"""The control fails the comparison a run makes, and the reference put in
the program's place passes it."""
import dataclasses

from bench import control, harness, traffic
from conftest import ROOT

CELLS = ["livejournal1m-replay-churn", "livejournal1m-serve-poisson"]


def _plan(cell):
    """The cell's plan with a partition capacity the small stream fills,
    so that K scales and placement matters."""
    p = harness.plan(ROOT, cell)
    p.config["session"]["engine"]["max_cap"] = 300
    return p


def _stream(p, events, seed):
    mix = dataclasses.replace(harness.churn_mix(p), lead_in_adds=2048)
    return traffic.powerlaw_churn(1 << 16, events, max_deg=24, seed=seed,
                                  mix=mix)


def test_control_is_not_correct():
    for cell in CELLS:
        p = _plan(cell)
        for seed in (3, 2**31 + 3):
            s = _stream(p, 6000, seed)
            checks, _ = control.control_checks(p, s, 6000, seed)
            assert not all(c.ok for c in checks), (cell, seed, checks)


def test_reference_in_the_programs_place_is_correct():
    from bench import reference
    p = _plan("livejournal1m-replay-churn")
    sess = p.config["session"]
    s = _stream(p, 6000, 5)
    ref = reference.run_reference(s.etype, s.vertex, s.nbrs, s.n,
                                  knobs=reference.Knobs(**sess["engine"]),
                                  seed=5)
    snap = control.as_snapshot(ref, s, 6000, 5)
    checks = harness.compare(snap, ref, 5)
    assert all(c.ok for c in checks), checks
