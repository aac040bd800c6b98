"""The benchmark's copies equal the program's versions at small sizes: the
churn generator, the arrival schedule, and the plain reference."""
import numpy as np
import pytest

from bench import reference, traffic


@pytest.mark.parametrize("n, events, seed, block", [
    (1 << 14, 3000, 3, 64),
    (1 << 20, 6000, 2**31 + 5, 1 << 16),
    (5000, 2000, 7, 100),
])
def test_powerlaw_churn_equals_program(n, events, seed, block):
    from repro.graph.stream import powerlaw_churn
    want = powerlaw_churn(n, events, max_deg=24, seed=seed)
    got = traffic.powerlaw_churn(n, events, max_deg=24, seed=seed,
                                 block_rows=block)
    assert np.array_equal(got.etype, want.etype)
    assert np.array_equal(got.vertex, want.vertex)
    assert np.array_equal(got.nbrs, want.nbrs)
    assert got.truncated_nbrs == want.truncated_nbrs


def test_churn_mix_is_a_parameter():
    s = traffic.powerlaw_churn(1 << 14, 4000, max_deg=24, seed=1,
                               mix=traffic.ChurnMix(vertex_delete_share=0.0,
                                                    edge_delete_share=0.0))
    assert np.all(s.etype == traffic.EVENT_ADD)
    assert len(np.unique(s.vertex)) == s.num_events


def test_poisson_arrivals_equals_program():
    from repro.graph.stream import VertexStream, poisson_arrivals
    n = 5000
    s = VertexStream(np.zeros(n, np.int32), np.zeros(n, np.int32),
                     np.zeros((n, 1), np.int32), n)
    want = poisson_arrivals(s, rate=1234.5, mean_batch=24.0, seed=9)
    got = traffic.poisson_arrivals(n, rate=1234.5, mean_batch=24.0, seed=9)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


@pytest.mark.parametrize("policy", ["sdp", "greedy", "ldg", "random",
                                    "hash", "fennel"])
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_reference_equals_program_oracle(policy, seed):
    from repro.core.config import EngineConfig
    from repro.core.ref import run_reference
    from repro.graph.stream import VertexStream
    eng = dict(k_max=16, k_init=1, autoscale=True, max_cap=300)
    s = traffic.powerlaw_churn(1 << 12, 1500, max_deg=16, seed=seed)
    want = run_reference(VertexStream(s.etype, s.vertex, s.nbrs, s.n),
                         policy=policy, cfg=EngineConfig(**eng), seed=seed)
    got = reference.run_reference(s.etype, s.vertex, s.nbrs, s.n,
                                  policy=policy,
                                  knobs=reference.Knobs(**eng), seed=seed)
    assert got.assignment == want.assignment
    assert got.adj == want.adj
    assert got.edge_load == want.edge_load
    assert got.vertex_count == want.vertex_count
    assert got.active == want.active
    assert (got.total_edges, got.cut_edges, got.scale_events, got.denied) \
        == (want.total_edges, want.cut_edges, want.scale_events, want.denied)
    assert np.array_equal(got.cut_matrix, want.cut_matrix)
    if policy == "sdp":
        assert got.scale_events > 0
