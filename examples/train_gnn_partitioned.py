"""End-to-end driver: SDP-partitioned distributed GNN training.

    PYTHONPATH=src python examples/train_gnn_partitioned.py --steps 30

This is the paper's technique working as a first-class framework feature:
  1. a graph arrives as a stream → SDP partitions it online (Alg. 1);
  2. the partition becomes the device layout: nodes are blocked per shard
     (repro.graph.halo), and every message-passing layer exchanges ONLY the
     published boundary rows (halo exchange under shard_map) — the
     collective volume is the edge-cut SDP minimised;
  3. a PNA-style GNN trains data-distributed over N host devices, with the
     hash-partition layout run side-by-side to show the communication win.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse          # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.api import Partitioner                               # noqa: E402
from repro.core import EngineConfig                             # noqa: E402
from repro.graph.generators import make_graph                   # noqa: E402
from repro.graph.halo import build_halo_spec, scatter_nodes     # noqa: E402
from repro.graph import stream as gstream                       # noqa: E402
from repro.models import layers as L                            # noqa: E402
from repro.optim.optimizers import adamw, apply_updates         # noqa: E402
from repro.runtime.gnn_sharded import make_sharded_aggregate    # noqa: E402


def build_layout(g, policy, n_shards):
    s = gstream.build_stream(g, seed=0)
    cfg = EngineConfig(k_max=n_shards, k_init=n_shards, autoscale=False)
    part = Partitioner.from_stream(s, cfg, policy=policy).feed(s)
    m = part.metrics()
    assign = np.array(part.state.assignment)
    assign[assign < 0] = 0
    spec = build_halo_spec(g, assign, n_shards)
    return spec, m


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--nodes", type=int, default=600)
    p.add_argument("--hidden", type=int, default=32)
    args = p.parse_args()

    from repro.launch.mesh import make_mesh
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    g = make_graph("mesh", args.nodes, 3 * args.nodes, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((g.n, args.hidden)).astype(np.float32)
    targets = rng.standard_normal((g.n, 1)).astype(np.float32)

    for policy in ("sdp", "hash"):
        spec, m = build_layout(g, policy, n_dev)
        agg = make_sharded_aggregate(mesh, spec)
        xb = jnp.asarray(scatter_nodes(spec, feats))      # (P, Nb, F)
        yb = jnp.asarray(scatter_nodes(spec, targets))
        maskb = jnp.asarray(scatter_nodes(
            spec, np.ones((g.n, 1), np.float32)))
        halo_args = tuple(jnp.asarray(a) for a in
                          (spec.publish_idx, spec.halo_map, spec.senders,
                           spec.receivers))

        key = jax.random.PRNGKey(0)
        params = {
            "w1": L.dense_init(key, args.hidden, args.hidden)["w"],
            "w2": L.dense_init(jax.random.fold_in(key, 1),
                               args.hidden, 1)["w"],
        }
        opt = adamw(3e-3, weight_decay=0.0)
        opt_state = opt.init(params)

        def loss_fn(params, xb):
            h = jnp.tanh(xb @ params["w1"])
            aggd = agg(h, *halo_args)                     # halo exchange
            pred = aggd @ params["w2"]
            return jnp.sum(((pred - yb) ** 2) * maskb) / jnp.sum(maskb)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(loss_fn)(params, xb)
            upd, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, upd), opt_state, loss

        losses = []
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        dt = time.perf_counter() - t0

        vol = spec.collective_bytes_per_layer(args.hidden)
        print(f"[{policy:4s}] edge-cut={m['edge_cut_ratio']:.4f} "
              f"halo-bytes/layer={vol/1e3:.1f}KB "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"({args.steps} steps, {dt:.1f}s, {n_dev} devices)")
    print("\nSDP's lower edge-cut translates 1:1 into lower halo-exchange"
          "\nvolume — the distributed-training win the paper's partitioner"
          "\nbuys (see EXPERIMENTS.md §Perf for the ogb_products version).")


if __name__ == "__main__":
    main()
