"""Step-builder and HLO-stats coverage: bundle construction for every
cell family, collective wire-byte formulas, and a small-mesh recsys
compile."""
import os
import subprocess
import sys
import textwrap


from repro.launch.hlo_stats import (_collective_wire, _shape_elems_bytes,
                                    _split_type_op, Instr)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# hlo_stats unit coverage
# ---------------------------------------------------------------------------

def _instr(op, type_str, line):
    return Instr("x", op, type_str, "", line)


def test_collective_wire_formulas():
    line = "replica_groups=[2,4]<=[8]"       # 2 groups of 4
    by = 4 * 1024 * 1024                      # f32[1024,1024]
    t = "f32[1024,1024]{1,0}"
    op, nbytes, wire = _collective_wire(_instr("all-gather", t, line), 8)
    assert nbytes == by and abs(wire - by * 3 / 4) < 1
    _, _, wire = _collective_wire(_instr("all-reduce", t, line), 8)
    assert abs(wire - 2 * by * 3 / 4) < 1
    _, _, wire = _collective_wire(_instr("reduce-scatter", t, line), 8)
    assert abs(wire - by * 3) < 1
    _, _, wire = _collective_wire(_instr("collective-permute", t, line), 8)
    assert wire == by


def test_shape_parsing_tuple_types():
    elems, nbytes = _shape_elems_bytes(
        "(f32[8,4]{1,0}, bf16[16]{0}, s32[])")
    assert elems == 32 + 16 + 1
    assert nbytes == 128 + 32 + 4


def test_split_type_op_handles_index_comments():
    t, op = _split_type_op(
        "(s32[], f32[8,64]{1,0}, /*index=5*/f32[4]{0}) while(%tuple.54), "
        "condition=%c, body=%b")
    assert op == "while"
    assert t.endswith(")")


def test_split_type_op_plain():
    t, op = _split_type_op("f32[512,128]{1,0} dot(%a, %b), "
                           "lhs_contracting_dims={1}")
    assert (t, op) == ("f32[512,128]{1,0}", "dot")


# ---------------------------------------------------------------------------
# step builders: every family constructs a coherent bundle on the
# production mesh shape (no compile — specs/shardings only)
# ---------------------------------------------------------------------------

BUILDER_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.configs import ARCHS
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

mesh = make_production_mesh(multi_pod=False)
cells = [("gemma2-9b", "train_4k", "opt"),
         ("moonshot-v1-16b-a3b", "prefill_32k", "baseline"),
         ("deepseek-coder-33b", "decode_32k", "baseline"),
         ("nequip", "molecule", "baseline"),
         ("meshgraphnet", "ogb_products", "halo"),
         ("two-tower-retrieval", "retrieval_cand", "baseline")]
for arch, shape, scheme in cells:
    b = build_step(arch, shape, mesh, scheme)
    flat_specs = jax.tree.leaves(b.specs)
    flat_sh = jax.tree.leaves(b.in_shardings,
                              is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat_specs) > 0 and len(flat_sh) > 0
    assert b.meta.get("model_flops", 0) > 0, (arch, shape)
    # every sharding must be addressable on this mesh
    for sh in flat_sh:
        assert sh.mesh.devices.size == 256
print("BUNDLES_OK")
"""

RECSYS_SMALL_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, dataclasses
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch
from repro.models import recsys as RS

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(get_arch("two-tower-retrieval").smoke_config,
                          user_vocab=4096, item_vocab=4096)
params = RS.init_params(jax.random.PRNGKey(0), cfg)
psh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
psh["user_table"] = NamedSharding(mesh, P("model", None))
psh["item_table"] = NamedSharding(mesh, P("model", None))
batch = {k: jnp.asarray(v) for k, v in RS.make_batch(cfg, 32).items()}
bsh = {"user_ids": NamedSharding(mesh, P("data", None, None)),
       "item_ids": NamedSharding(mesh, P("data", None, None)),
       "log_q": NamedSharding(mesh, P("data"))}
with mesh:
    loss, _ = jax.jit(lambda p, b: RS.loss_fn(p, b, cfg),
                      in_shardings=(psh, bsh))(params, batch)
import numpy as np
assert np.isfinite(float(loss))
print("RECSYS_SHARDED_OK", float(loss))
"""


def _run(code):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_bundles_construct_on_production_mesh():
    assert "BUNDLES_OK" in _run(BUILDER_CODE)


def test_recsys_sharded_loss_runs():
    """Row-sharded embedding tables produce a finite loss end-to-end on a
    real multi-device mesh (the production recsys layout, scaled down)."""
    assert "RECSYS_SHARDED_OK" in _run(RECSYS_SMALL_CODE)
