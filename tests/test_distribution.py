"""Distribution layer: halo exchange vs naive aggregation, sharding rules,
hlo_stats loop-aware analysis, small-mesh step compilation, elastic
re-shard. Uses a subprocess with forced host devices where a multi-device
mesh is required (the main test process keeps the default 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np


from repro.core import EngineConfig, run_stream
from repro.graph.generators import make_graph
from repro.graph.halo import build_halo_spec, gather_nodes, scatter_nodes
from repro.graph import stream as gstream
from repro.launch.hlo_stats import analyze
from repro.runtime import sharding as SHR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# hlo_stats (single-device, no mesh needed)
# ---------------------------------------------------------------------------

def test_hlo_stats_loop_free_matches_cost_analysis():
    def f(x, w1, w2):
        return ((x @ w1) @ w2).sum()
    specs = [jax.ShapeDtypeStruct(s, jnp.float32)
             for s in ((64, 128), (128, 256), (256, 64))]
    co = jax.jit(f).lower(*specs).compile()
    st = analyze(co.as_text(), 1)
    expect = 2 * 64 * 128 * 256 + 2 * 64 * 256 * 64
    assert abs(st["flops_per_device"] - expect) / expect < 1e-6


def test_hlo_stats_scan_multiplies_trip_count():
    def g(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), ()
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()
    specs = [jax.ShapeDtypeStruct((32, 64), jnp.float32),
             jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)]
    co = jax.jit(g).lower(*specs).compile()
    st = analyze(co.as_text(), 1)
    expect = 2 * 32 * 64 * 64 * 6
    assert abs(st["flops_per_device"] - expect) / expect < 1e-6
    # XLA's own analysis undercounts by the trip count — that's the bug
    # this module exists to fix
    ca = co.cost_analysis()
    # jax 0.4.x returns a one-element list of dicts, newer jax a dict
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert ca["flops"] < st["flops_per_device"]


# ---------------------------------------------------------------------------
# halo exchange (multi-device via subprocess)
# ---------------------------------------------------------------------------

HALO_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import EngineConfig, run_stream
from repro.graph.generators import make_graph
from repro.graph import stream as gstream
from repro.graph.halo import build_halo_spec, scatter_nodes, gather_nodes
from repro.runtime.gnn_sharded import make_sharded_aggregate, naive_aggregate

g = make_graph("mesh", 96, 260, seed=0)
s = gstream.build_stream(g, seed=0)
st, _ = run_stream(s, policy="sdp",
                   cfg=EngineConfig(k_max=4, k_init=4, autoscale=False))
assign = np.array(st.assignment); assign[assign < 0] = 0
spec = build_halo_spec(g, assign, 4)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
x = np.random.default_rng(0).standard_normal((g.n, 8)).astype(np.float32)
blocks = scatter_nodes(spec, x)
agg = make_sharded_aggregate(mesh, spec)
out = agg(jnp.asarray(blocks), jnp.asarray(spec.publish_idx),
          jnp.asarray(spec.halo_map), jnp.asarray(spec.senders),
          jnp.asarray(spec.receivers))
e = g.edge_array()
snd = np.concatenate([e[:, 0], e[:, 1]])
rcv = np.concatenate([e[:, 1], e[:, 0]])
ref = naive_aggregate(jnp.asarray(x), jnp.asarray(snd), jnp.asarray(rcv))
np.testing.assert_allclose(gather_nodes(spec, np.asarray(out)),
                           np.asarray(ref), rtol=1e-5, atol=1e-5)
print("HALO_OK", spec.publish_size, spec.halo_size)
"""


def test_halo_aggregation_matches_naive():
    out = _run_subprocess(HALO_CODE)
    assert "HALO_OK" in out


def test_halo_collective_volume_tracks_edge_cut():
    """SDP partitioning must shrink the halo (collective bytes) vs hash."""
    g = make_graph("mesh", 400, 1100, seed=1)
    s = gstream.build_stream(g, seed=1)
    pub = {}
    for pol in ("sdp", "hash"):
        st, _ = run_stream(s, policy=pol,
                           cfg=EngineConfig(k_max=4, k_init=4,
                                            autoscale=False))
        a = np.array(st.assignment)
        a[a < 0] = 0
        spec = build_halo_spec(g, a, 4)
        # true (unpadded) boundary volume = rows actually published
        pub[pol] = int((spec.publish_idx >= 0).sum())
    # distinct-boundary-vertex volume saturates at small k, so the factor
    # is milder than the 2× edge-cut gap — but must track direction
    assert pub["sdp"] < 0.8 * pub["hash"], pub


def test_scatter_gather_roundtrip():
    g = make_graph("mesh", 50, 140, seed=2)
    assign = np.random.default_rng(0).integers(0, 3, g.n)
    spec = build_halo_spec(g, assign, 3)
    x = np.random.default_rng(1).standard_normal((g.n, 5)).astype(np.float32)
    blocks = scatter_nodes(spec, x)
    back = gather_nodes(spec, blocks)
    np.testing.assert_array_equal(back, x)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_lm_param_rules_cover_all_paths():
    from repro.configs import ARCHS
    from repro.models import transformer as T
    import functools
    for arch_id in ("gemma2-9b", "moonshot-v1-16b-a3b"):
        cfg = ARCHS[arch_id].config
        like = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                              jax.random.PRNGKey(0))
        paths, vals, _ = SHR.tree_paths(like)
        rules = SHR.lm_param_rules_probe() if hasattr(
            SHR, "lm_param_rules_probe") else None
        # every 2D+ tensor must match a non-replicated rule
        import re
        rule_list = [
            (r"embed$", 1), (r"lm_head$", 1), (r"attn/w[qkvo]$", 1),
            (r"mlp/w[igo]$", 1), (r"moe/router$", 1), (r"moe/w[igo]$", 1),
            (r"ln", 0),
        ]
        for p, v in zip(paths, vals):
            matched = any(re.search(pat, p) for pat, _ in rule_list)
            assert matched, f"param path {p} matches no sharding rule"


def test_shape_divisibility_for_production_mesh():
    """Every LM arch's TP/FSDP dims divide the 16×16 and 2×16×16 meshes."""
    from repro.configs import ARCHS
    for arch_id, arch in ARCHS.items():
        if arch.family != "lm":
            continue
        cfg = arch.config
        for tp in (16,):
            assert (cfg.n_heads * cfg.head_dim) % tp == 0, arch_id
            assert (cfg.n_kv_heads * cfg.head_dim) % tp == 0, arch_id
            assert cfg.d_ff % tp == 0 or cfg.moe is not None, arch_id
            assert cfg.vocab % tp == 0, arch_id
        for fsdp in (16, 32):
            assert cfg.d_model % fsdp == 0, arch_id
        if cfg.moe is not None:
            assert cfg.moe.n_experts % 16 == 0 or cfg.moe.n_experts <= 16, \
                arch_id


# ---------------------------------------------------------------------------
# end-to-end small-mesh compile (the dry-run path on 8 devices)
# ---------------------------------------------------------------------------

SMALL_DRYRUN_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.steps import build_step
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
b = build_step("pna", "molecule", mesh)
with mesh:
    co = jax.jit(b.fn, in_shardings=b.in_shardings,
                 out_shardings=b.out_shardings,
                 donate_argnums=b.donate).lower(*b.specs).compile()
print("COMPILED", co.memory_analysis().temp_size_in_bytes > 0)
"""


def test_small_mesh_step_compiles():
    out = _run_subprocess(SMALL_DRYRUN_CODE)
    assert "COMPILED" in out


# ---------------------------------------------------------------------------
# elastic re-shard
# ---------------------------------------------------------------------------

ELASTIC_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.runtime.elastic import ElasticRunner
import tempfile

def mesh_factory(devices):
    n = len(devices)
    return jax.sharding.Mesh(np.asarray(devices).reshape(n, 1),
                             ("data", "model"))

def shardings_fn(mesh, tree):
    return jax.tree.map(
        lambda x: NamedSharding(mesh, P("data") if np.ndim(x) >= 1
                                and np.shape(x)[0] % mesh.shape["data"] == 0
                                else P()), tree,
        is_leaf=lambda x: hasattr(x, "shape"))

params = {"w": jnp.arange(32, dtype=jnp.float32)}
opt = {"mu": jnp.zeros(32)}
with tempfile.TemporaryDirectory() as d:
    runner = ElasticRunner(mesh_factory, shardings_fn,
                           CheckpointManager(d, interval=1))
    st = runner.place(jax.devices()[:8], params, opt, step=3)
    st2 = runner.rescale(st, jax.devices()[:4])   # scale-in: 8 -> 4
    np.testing.assert_array_equal(np.asarray(st2.params["w"]),
                                  np.arange(32, dtype=np.float32))
    assert st2.mesh.shape["data"] == 4
    st3 = runner.rescale(st2, jax.devices()[:8])  # scale-out: 4 -> 8
    np.testing.assert_array_equal(np.asarray(st3.params["w"]),
                                  np.arange(32, dtype=np.float32))
print("ELASTIC_OK")
"""


def test_elastic_rescale_preserves_state():
    out = _run_subprocess(ELASTIC_CODE)
    assert "ELASTIC_OK" in out
