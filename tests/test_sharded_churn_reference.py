"""A vertex-sharded session on four devices against the benchmark's plain
reference, at the degree law of the ``friendster-32m`` deployment.

One subprocess (four host CPU devices) draws a churn stream with the
benchmark's generator (``bench/traffic.powerlaw_churn`` at minimum degree
8 and exponent 2.41, a snapshot lead-in of adds, then 70/15/15 adds,
vertex deletes and edge deletes), feeds it in uneven chunks through
``Partitioner(sharded=True, shard_devices=4)`` with K scaling out and in,
and compares the session's state with ``bench/reference.run_reference``
through the harness's own ``snapshot`` and ``compare``: the yardstick
that decides a benchmark run's ``correct``. The tests below read what it
reports.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re, sys, time
sys.path[:0] = [REPO, os.path.join(REPO, "src")]
import jax
from bench import harness, reference, traffic
from repro.api import Partitioner
from repro.core.config import EngineConfig
from repro.runtime import telemetry

N, D, W, SNAP, T, SEED = 1 << 12, 48, 32, 768, 3072, 2**31 + 5
CAP = 1500
mix = traffic.ChurnMix(min_degree=8, degree_exponent=2.41, lead_in_adds=SNAP)
s = traffic.powerlaw_churn(N, T, max_deg=D, seed=SEED, mix=mix)
cfg = EngineConfig(k_max=16, k_init=1, autoscale=True, max_cap=CAP)
part = Partitioner(cfg, n=N, max_deg=D, policy="sdp", window=W, seed=SEED,
                   sharded=True, shard_devices=4)
t0 = time.perf_counter()
sizes = [300, 17, 256, 1, 511, 64, 33]
lo, i = 0, 0
while lo < T:
    hi = min(lo + sizes[i % len(sizes)], T)
    part.feed(s.chunk(lo, hi))
    lo, i = hi, i + 1
part.sync()
m = part.metrics()

jitted = []
real_jit = jax.jit
def spy(fn, **kw):
    j = real_jit(fn, **kw)
    def call(*args):
        jitted.append((j, args))
        return j(*args)
    return call
jax.jit = spy
snap = harness.snapshot(part.state, s, T)
jax.jit = real_jit
j, args = jitted[-1]
hlo = j.lower(*args).compile().as_text()

ref = reference.run_reference(
    s.etype, s.vertex, s.nbrs, N, policy="sdp",
    knobs=reference.Knobs(k_max=16, k_init=1, autoscale=True, max_cap=CAP),
    seed=SEED)
checks = {c.name: c.value for c in harness.compare(snap, ref, SEED)}

rec = telemetry.spans(since=t0)
dispatch = [r for r in rec if r.name == "session.dispatch"]
puts = [r for r in rec if r.name == "session.shard_put"]
inside = all(
    p.parent == "session.dispatch" and any(
        d.attrs["path"] == "sharded" and d.start <= p.start
        and p.end <= d.end for d in dispatch)
    for p in puts)
block = [sh.data.shape for sh in part.state.adj.addressable_shards]
print(json.dumps({
    "checks": checks,
    "events": int(T),
    "scale_events": int(ref.scale_events),
    "denied": int(ref.denied),
    "windows": m["windows"],
    "pad_slots": int(m["pad_slots"]),
    "shard_devices": int(m["shard_devices"]),
    "per_device_state_bytes": int(m["per_device_state_bytes"]),
    "adj_blocks": [list(b) for b in block],
    "shard_puts": len(puts),
    "shard_put_bytes": sorted({p.attrs["bytes"] for p in puts}),
    "shard_puts_inside_dispatch": bool(inside),
    "all_gathers": len(re.findall(r" all-gather(?:-start)?\\(", hlo)),
    "all_reduces": len(re.findall(r" all-reduce(?:-start)?\\(", hlo)),
}))
"""


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = f"REPO = {REPO!r}\n" + textwrap.dedent(CODE)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_state_is_the_reference_state(run):
    """Assignment, presence, every touched adjacency row, no stray
    adjacency entry and the whole K-state equal the reference's, with K
    scaling out and back in and no scale-out denied."""
    assert run["checks"] == {
        "assignment_mismatch": 0, "present_mismatch": 0,
        "adj_row_mismatch": 0, "adj_untouched_entries": 0,
        "k_state_mismatch": 0}
    assert run["scale_events"] > 100 and run["denied"] == 0


def test_every_slice_rides_the_sharded_window(run):
    """Each uneven slice is fed as whole windows of the sharded program,
    its tail padded; nothing takes a dense path."""
    w = run["windows"]
    assert w["sharded"] > 0
    assert sum(w.values()) == w["sharded"]
    assert w["sharded"] * 32 == run["events"] + run["pad_slots"]
    assert run["pad_slots"] > 0


def test_state_is_split_in_four_row_blocks(run):
    """The adjacency lives as four (n/4, max_deg) blocks, one a device,
    and ``per_device_state_bytes`` counts one block of every row leaf
    plus the replicated K-state."""
    n, d, k = 1 << 12, 48, 16
    assert run["shard_devices"] == 4
    assert run["adj_blocks"] == [[n // 4, d]] * 4
    rows = (n // 4) * (4 + 1 + 4 * d)            # assignment, present, adj
    k_state = 4 * k + 4 * k + k + 4 * k * k + 5 * 4 + 2 * 4
    assert run["per_device_state_bytes"] == rows + k_state


def test_one_shard_put_per_window_inside_its_dispatch(run):
    """Each sharded window places its padded event tensors on the mesh in
    one ``session.shard_put`` span, inside that window's dispatch."""
    assert run["shard_puts"] == run["windows"]["sharded"]
    assert run["shard_puts_inside_dispatch"]
    w, d = 32, 48
    assert run["shard_put_bytes"] == [4 * (w + w + w * d + 1)]


def test_harness_snapshot_does_not_gather_the_adjacency(run):
    """The harness's copy of the touched rows reads each row on the device
    that holds it and sums the pieces: no all-gather of ``adj``."""
    assert run["all_gathers"] == 0
    assert run["all_reduces"] >= 1
