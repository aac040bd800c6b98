"""Partition-aware sharded GNN aggregation (shard_map + halo exchange).

Baseline distribution (pjit, edge-sharded segment-sum) all-reduces the full
(N, F) node tensor every layer. With an SDP HaloSpec, each layer instead
all-gathers only the published boundary rows — collective bytes scale with
the edge-cut the paper minimises. See EXPERIMENTS.md §Perf (GNN hillclimb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.graph.halo import HaloSpec
from repro.models.gnn import common as C


def halo_aggregate(x_blk, publish_idx, halo_map, senders, receivers,
                   *, axis: str, block_size: int):
    """Per-device body: x_blk (Nb, F) local block → aggregated (Nb, F).

    One all-gather of the published boundary rows replaces the full-tensor
    all-reduce of the naive layout.
    """
    pub = jnp.take(x_blk, jnp.maximum(publish_idx, 0), axis=0)
    pub = jnp.where((publish_idx >= 0)[:, None], pub, 0.0)      # (B_max, F)
    allpub = jax.lax.all_gather(pub, axis)                      # (P, B_max, F)
    hs, hp = halo_map[:, 0], halo_map[:, 1]
    halo = allpub[jnp.maximum(hs, 0), jnp.maximum(hp, 0)]       # (H_max, F)
    halo = jnp.where((hs >= 0)[:, None], halo, 0.0)
    buf = jnp.concatenate([x_blk, halo], axis=0)                # (Nb+H, F)
    msg = jnp.take(buf, jnp.maximum(senders, 0), axis=0)
    msg = jnp.where((senders >= 0)[:, None], msg, 0.0)
    return C.segment_sum_pad(msg, receivers, block_size)


def make_sharded_aggregate(mesh, spec: HaloSpec, axis: str = "data"):
    """Returns agg(x_blocks (P, Nb, F)) -> (P, Nb, F) running under
    shard_map with the halo exchange on `axis`."""

    def agg(x_blocks, publish_idx, halo_map, senders, receivers):
        body = functools.partial(halo_aggregate, axis=axis,
                                 block_size=spec.block_size)

        def shard_body(x, pi, hm, sn, rc):
            return body(x[0], pi[0], hm[0], sn[0], rc[0])[None]

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )(x_blocks, publish_idx, halo_map, senders, receivers)

    return agg


def naive_aggregate(x, senders, receivers):
    """Baseline: global-id segment-sum; under pjit the node tensor is
    replicated/all-reduced every layer (the thing SDP avoids)."""
    n = x.shape[0]
    msg = jnp.take(x, jnp.maximum(senders, 0), axis=0)
    msg = jnp.where((senders >= 0)[:, None], msg, 0.0)
    return C.segment_sum_pad(msg, receivers, n)
