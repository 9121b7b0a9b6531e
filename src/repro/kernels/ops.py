"""jit'd public wrappers for the aggregation kernels.

On TPU the Pallas kernels run compiled (interpret=False); everywhere
else (this CPU container, unit tests) they run in interpret mode or
fall back to the jnp reference — selected once at import.  Both paths
are numerically validated against ref.py in tests/test_kernels.py.

``d_blk`` is None everywhere but in tests: the kernels then derive their
column block from G's shape (``brsgd_stats._tiling``); a number forces
that block, to exercise many blocks and ragged tails at small widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .brsgd_stats import (brsgd_partials_pallas, brsgd_stats_pallas,
                          cwise_median_pallas, fused_stats_pallas,
                          masked_mean_pallas, select_mean_pallas,
                          trimmed_mean_pallas)

_BACKEND = jax.default_backend()
_INTERPRET = _BACKEND != "tpu"
# Pallas interpret mode is Python-slow for large d; production (TPU) runs
# compiled.  On CPU we default to the jnp reference for speed and keep
# the interpret path exercised by the kernel test-suite.
_USE_PALLAS_DEFAULT = _BACKEND == "tpu"


def default_use_pallas() -> bool:
    """Import-time kernel-vs-reference default (True iff on TPU)."""
    return _USE_PALLAS_DEFAULT


@functools.partial(jax.jit, static_argnames=("use_pallas", "d_blk"))
def brsgd_stats(G, use_pallas: bool = _USE_PALLAS_DEFAULT,
                d_blk: int | None = None):
    """G [m,d] -> (median [d], mean [d], scores [m], l1 [m])."""
    if use_pallas:
        return brsgd_stats_pallas(G, d_blk=d_blk, interpret=_INTERPRET)
    return ref.brsgd_stats_ref(G)


@functools.partial(jax.jit, static_argnames=("needs", "axis", "use_pallas",
                                             "d_blk"))
def fused_stats(G, needs: tuple, axis: int = 0,
                use_pallas: bool = _USE_PALLAS_DEFAULT,
                d_blk: int | None = None, valid=None, rows=None,
                refs=None) -> dict:
    """Fused statistics pass: any subset of ``ref.STAT_NAMES`` from one
    read of G (DESIGN.md §Perf).

    ``axis`` indexes the m workers; G may be N-D (blocked-scope views
    keep the worker axis mid-leaf).  On TPU the worker-major 2-D case
    runs the single-HBM-read Pallas kernel; everywhere else the jnp
    reference shares ONE bitonic sorted-rows pass across the requested
    statistics.  ``needs`` must be hashable (tuple/frozenset); unknown
    names are rejected by the engine registry before reaching here.

    ``valid`` ([m] 0/1) switches to the elastic masked pass (DESIGN.md
    §Elastic): statistics of the active workers only, dropped slots as
    exact zeros.  ``rows``/``refs`` are the streaming-accumulator hooks
    (per-arrival-bucket output slots / shared active-set invariants) —
    see ``engine.stream_leaf_stats``.  The Pallas kernels assume a full
    worker set, so masked calls always take the jnp reference.
    """
    needs = tuple(n for n in ref.STAT_NAMES if n in needs)
    if not needs:
        return {}
    if valid is not None:
        return ref.masked_fused_stats_ref(G, needs, valid, axis=axis,
                                          rows=rows, refs=refs)
    if use_pallas and axis == 0 and G.ndim == 2:
        return fused_stats_pallas(G, needs, d_blk=d_blk,
                                  interpret=_INTERPRET)
    return ref.fused_stats_ref(G, needs, axis=axis)


def masked_stat_refs(G, needs: tuple, valid, axis: int = 0) -> dict:
    """Shared active-set invariants for the streaming accumulator — see
    ``ref.masked_stat_refs`` (computed once per leaf, reused by every
    arrival bucket's ``fused_stats(..., rows=bucket, refs=...)``)."""
    needs = tuple(n for n in ref.STAT_NAMES if n in needs)
    return ref.masked_stat_refs(G, needs, valid, axis=axis)


@functools.partial(jax.jit, static_argnames=("use_pallas", "d_blk"))
def brsgd_partials(G, use_pallas: bool = _USE_PALLAS_DEFAULT,
                   d_blk: int | None = None):
    """G [m,d] -> (scores [m], l1 [m]) — the stats pass without the
    [d]-sized median/mean outputs (first pass of the fused BrSGD path)."""
    st = fused_stats(G, ("scores", "l1"), use_pallas=use_pallas, d_blk=d_blk)
    return st["scores"], st["l1"]


@functools.partial(jax.jit, static_argnames=("beta", "use_pallas", "d_blk"))
def brsgd_select_combine(G, scores, l1, beta: float, threshold,
                         use_pallas: bool = _USE_PALLAS_DEFAULT,
                         d_blk: int | None = None):
    """Fused C1∩C2 selection + masked mean (second pass of the fused
    BrSGD path).  Returns (aggregate [d], selection weights [m])."""
    if use_pallas:
        return select_mean_pallas(G, scores, l1, beta, threshold,
                                  d_blk=d_blk, interpret=_INTERPRET)
    # jnp fallback: the shared selection math + deterministic combine
    sel, _, _, _ = ref.brsgd_select_mask(scores, l1, beta, threshold)
    w = sel.astype(jnp.float32)
    return ref.masked_mean_det(G, w), w


@functools.partial(jax.jit, static_argnames=("use_pallas", "d_blk"))
def masked_mean(G, mask, use_pallas: bool = _USE_PALLAS_DEFAULT,
                d_blk: int | None = None):
    """Masked (bool) or weighted (f32) row mean: Σ w_i g_i / Σ w_i."""
    if use_pallas:
        return masked_mean_pallas(G, mask, d_blk=d_blk, interpret=_INTERPRET)
    return ref.masked_mean_ref(G, mask)


@functools.partial(jax.jit, static_argnames=("use_pallas", "d_blk"))
def cwise_median(G, use_pallas: bool = _USE_PALLAS_DEFAULT,
                 d_blk: int | None = None, valid=None):
    if valid is not None:
        return ref.masked_cwise_median_ref(G, valid)
    if use_pallas:
        return cwise_median_pallas(G, d_blk=d_blk, interpret=_INTERPRET)
    return ref.cwise_median_ref(G)


@functools.partial(jax.jit, static_argnames=("trim_frac", "use_pallas",
                                             "d_blk"))
def trimmed_mean(G, trim_frac: float, use_pallas: bool = _USE_PALLAS_DEFAULT,
                 d_blk: int | None = None, valid=None):
    """Coordinate-wise trimmed mean (k = ⌊trim_frac·m⌋ per side; with a
    ``valid`` mask both counts are over the active rows, traced)."""
    if valid is not None:
        return ref.masked_trimmed_mean_ref(G, trim_frac, valid)
    if use_pallas:
        return trimmed_mean_pallas(G, trim_frac, d_blk=d_blk,
                                   interpret=_INTERPRET)
    return ref.trimmed_mean_ref(G, trim_frac)
