"""Pallas TPU kernel: partition-affinity scoring (paper Eq. 1, batched).

Computes, for a window of W streaming vertices with (already gathered)
neighbour partition labels ``labels[w, d] ∈ {-1, 0..K-1}``:

    scores[w, k] = |{d : labels[w, d] == k}|      (|E(v) ∩ P_k|)
    deg[w]       = |{d : labels[w, d] >= 0}|

TPU adaptation (DESIGN.md §2): the paper's Java hash-probe becomes a
VMEM-tiled compare+reduce. The (W, D) label block is compared against the
K partition ids broadcast in VREGs — an 8×128-lane-friendly elementwise
compare — and reduced over the neighbour axis D, accumulating the (bW, K)
score tile in VMEM across the D grid dimension. The arbitrary HBM gather
``assignment[rows]`` stays outside the kernel (XLA's native gather), which
is the right split on TPU: gathers don't use the MXU/VPU, histograms do.

Grid: (W/bW, D/bD); the D axis is the reduction/accumulation axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import label_histogram, resolve_interpret


def _affinity_kernel(labels_ref, scores_ref, deg_ref, *, k_max: int):
    d_idx = pl.program_id(1)

    @pl.when(d_idx == 0)
    def _init():
        scores_ref[...] = jnp.zeros_like(scores_ref)
        deg_ref[...] = jnp.zeros_like(deg_ref)

    labels = labels_ref[...]                                  # (bW, bD) int32
    scores, deg = label_histogram(labels, k_max)              # shared masking
    scores_ref[...] += scores                                 # (bW, K)
    deg_ref[...] += deg                                       # (bW, 1)


@functools.partial(
    jax.jit, static_argnames=("k_max", "block_w", "block_d", "interpret")
)
def partition_affinity(
    labels: jax.Array,
    *,
    k_max: int,
    block_w: int = 128,
    block_d: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(scores (W, K), deg (W,)) from neighbour partition labels (W, D).

    ``interpret=None`` defers to ``repro.kernels.common.default_interpret``
    — real Mosaic compile on a TPU backend, interpret mode elsewhere.
    """
    interpret = resolve_interpret(interpret)
    w, d = labels.shape
    bw = min(block_w, w)
    bd = min(block_d, d)
    pad_w = (-w) % bw
    pad_d = (-d) % bd
    if pad_w or pad_d:
        labels = jnp.pad(labels, ((0, pad_w), (0, pad_d)), constant_values=-1)
    wp, dp = labels.shape

    scores, deg = pl.pallas_call(
        functools.partial(_affinity_kernel, k_max=k_max),
        grid=(wp // bw, dp // bd),
        in_specs=[pl.BlockSpec((bw, bd), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bw, k_max), lambda i, j: (i, 0)),
            pl.BlockSpec((bw, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((wp, k_max), jnp.int32),
            jax.ShapeDtypeStruct((wp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(labels)
    return scores[:w], deg[:w, 0]
