"""Shared Pallas plumbing — ONE definition site for two conventions every
repro kernel must agree on:

* **interpret-mode policy** (``default_interpret``): kernels run in Pallas
  interpret mode exactly when the backend is not a TPU, so the same
  ``use_kernel=True`` call sites exercise the kernel logic bit-identically
  in CPU CI and compile to real Mosaic on TPU.

* **label-histogram masking** (``label_histogram``, ``row_histogram``):
  affinity scoring is a compare+reduce one-hot histogram over neighbour
  labels where ``-1`` means "no neighbour here" (absent vertex, padded
  slot, or padded tile) and matches no partition id. The batched
  committed-scores kernel (``partition_affinity``) scores through the
  first, the fused window chooser's slot loop through the second; both
  share the masking convention, so their semantics cannot drift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# TPU VPU lane tiling — kernels pad the (window/vertex, k) trailing dims to
# multiples of this when compiled for real hardware (interpret mode accepts
# any geometry; see docs/ARCHITECTURE.md "Kernels").
TILE = (8, 128)

def default_interpret() -> bool:
    """True ⇔ Pallas kernels should run in interpret mode: the backend is
    not a TPU. The one ``use_kernel=True`` flag thus means "real kernel on
    TPU, interpreted elsewhere"."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel call's ``interpret=None`` default to the policy."""
    return default_interpret() if interpret is None else bool(interpret)


def label_histogram(labels: jax.Array, k_max: int):
    """(…, D) int32 labels → ((…, K) scores, (…, 1) degree).

    ``scores[..., k] = |{j : labels[..., j] == k}|`` and ``degree`` counts
    labels ``>= 0``. Labels ``-1`` (absent / padding) match no k — THE
    masking convention shared by every scoring path; integer compare+sum,
    so results are exact and bit-identical across engines.
    """
    ks = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * labels.ndim + (k_max,), labels.ndim)
    onehot = (labels[..., None] == ks).astype(jnp.int32)
    scores = jnp.sum(onehot, axis=-2)
    deg = jnp.sum((labels >= 0).astype(jnp.int32), axis=-1, keepdims=True)
    return scores, deg


def row_histogram(labels: jax.Array, k_max: int):
    """(1, D) int32 labels → ((1, K) scores, scalar degree), in the shapes
    a Mosaic kernel lowers: a (K, D) compare against a sublane iota, a
    lane reduction to a (K, 1) column, and the column turned into a row
    through the diagonal of a (K, K) select. Same ``-1`` masking and the
    same exact integer counts as ``label_histogram``."""
    ks = jax.lax.broadcasted_iota(jnp.int32, (k_max, labels.shape[-1]), 0)
    col = jnp.sum((labels == ks).astype(jnp.int32), axis=1, keepdims=True)
    ri = jax.lax.broadcasted_iota(jnp.int32, (k_max, k_max), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (k_max, k_max), 1)
    scores = jnp.sum(jnp.where(ri == ci, col, 0), axis=0, keepdims=True)
    deg = jnp.sum((labels >= 0).astype(jnp.int32))
    return scores, deg
