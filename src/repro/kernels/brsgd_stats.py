"""Pallas TPU kernels: fused BrSGD aggregation statistics + combine.

The aggregation is memory-bound (O(1) FLOP per byte of G), so the win
on TPU is minimizing HBM traffic over G.  Kernels here:

* ``fused_stats_pallas``      ONE pass over G emitting any subset of
                              ``ref.STAT_NAMES`` (majority-score, l1,
                              d2med [m]; Gram [m, m]) — every statistic
                              an aggregator declares costs a single
                              shared HBM read, and the coordinate-wise
                              median inside the tile is computed once
                              for l1 AND d2med (the one-sort contract,
                              DESIGN.md §Perf).
* ``brsgd_stats_pallas``      one pass producing column mean [d],
                              coordinate-wise median [d], majority
                              scores and l1 [m].
* ``brsgd_partials_pallas``   fused_stats_pallas over (scores, l1) —
                              no [d]-sized median/mean HBM writes.
                              First pass of the fused BrSGD path.
* ``select_mean_pallas``      second pass fusing the C1∩C2 selection
                              (recomputed per grid step from the [m]
                              score/l1 vectors — trivially cheap) with
                              the masked-mean row combine.  With the
                              partials pass, local BrSGD streams G from
                              HBM exactly twice and never round-trips a
                              [d]-sized intermediate.
* ``masked_mean_pallas``      standalone masked/weighted row mean.
* ``cwise_median_pallas`` /   coordinate-wise median and trimmed mean:
  ``trimmed_mean_pallas``     one order-statistic kernel over the same
                              bitonic sorting network.

Tiling (the TPU's (8, 128) rule): grid over d; each step loads an
(m, d_blk) tile into VMEM — m <= 64 workers is a compile-time constant
and the block's full first dim, d_blk a multiple of 128 (default 2048
→ m*d_blk*4B = 512 KiB << 16 MiB VMEM).  G is zero-padded to a whole
number of tiles, so any leaf width runs.  Per-worker statistics are
[m, 1] column outputs (Gram: [m, m]) resident across the grid and
accumulated in place — the grid axis is ``"arbitrary"`` and step 0
zeroes them.  [d]-sized outputs are (1, d_blk) row blocks.  The
median/trim sort is a bitonic network over (1, d_blk) worker rows,
padded to a power of two with +inf — static compare-exchange stages of
jnp.minimum/maximum, MXU-free.  Row combines are exact f32
multiply-adds of (m, 1) weights over the tile; scalar thresholds ride
in SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

_LANES = 128
# resident [m]-sized accumulators need the grid run in order
_ACCUMULATE = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _tiling(G, d_blk: int):
    """Tile G's dim axis -> (G, d_blk, grid, n_pad_columns).  A leaf no
    wider than one tile is a single full-width block (any width is legal
    when the block spans the whole dim); wider leaves take lane-aligned
    tiles and are zero-padded to a whole number of them.  A zero
    column's median/mean is zero, its l1/trim contribution is zero, and
    its score contribution is +1 for EVERY worker (all tie at the mean)
    — the wrappers subtract that uniform offset."""
    d = G.shape[1]
    if d <= d_blk:
        return G, d, 1, 0
    d_blk = -(-d_blk // _LANES) * _LANES
    pad = (-d) % d_blk
    if pad:
        G = jnp.pad(G, ((0, 0), (0, pad)))
    return G, d_blk, G.shape[1] // d_blk, pad


def _sorted_rows(g, m: int):
    """Ascending worker rows of the f32 tile g [m, d_blk] — a list of
    (1, d_blk) rows, padded to a power of two with +inf rows that sort
    last — via the static bitonic network the jnp reference path runs
    (ref.bitonic_stages is the one copy)."""
    mp = 1 << max(1, math.ceil(math.log2(m)))
    rows = [g[i:i + 1, :] for i in range(m)]
    rows += [jnp.full_like(rows[0], jnp.inf)] * (mp - m)
    for stage in ref.bitonic_stages(mp):
        for i, l, asc in stage:
            lo = jnp.minimum(rows[i], rows[l])
            hi = jnp.maximum(rows[i], rows[l])
            rows[i], rows[l] = (lo, hi) if asc else (hi, lo)
    return rows


def _median_rows(g, m: int):
    """Coordinate-wise median (1, d_blk) via the bitonic network."""
    rows = _sorted_rows(g, m)
    if m % 2:
        return rows[(m - 1) // 2]
    return 0.5 * (rows[m // 2 - 1] + rows[m // 2])


def _majority_scores(g, m: int):
    """(column mean (1, d_blk), per-worker majority-score partials
    (m, 1) int32).  Tile counts are f32 sums of 0/1 (exact: a tile is
    far below 2^24 wide; Mosaic has no i1 truncation for bool->int
    casts) and leave as int32, so the count accumulated across the grid
    stays exact at any leaf width — an f32 running sum past 2^24 drops
    counts and can flip BrSGD's kth-score cut."""
    mean_c = jnp.sum(g, axis=0, keepdims=True) / m
    above = jnp.where(g >= mean_c, 1.0, 0.0)
    n_above = jnp.sum(above, axis=0, keepdims=True)
    M = jnp.where(n_above * 2 >= m, above, 1.0 - above)
    return mean_c, jnp.sum(M, axis=1, keepdims=True).astype(jnp.int32)


def _zero_at_first_step(refs):
    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in refs:
            r[...] = jnp.zeros(r.shape, r.dtype)


def _fused_stats_kernel(g_ref, *out_refs, m: int, needs: tuple):
    """One tile pass accumulating the requested subset of
    ref.STAT_NAMES into its resident outputs.

    ``needs`` is a canonical-order tuple matching ``out_refs``.  The
    tile's coordinate-wise median is computed at most once and shared by
    l1/d2med; the Gram partial is the tile's g @ gᵀ."""
    _zero_at_first_step(out_refs)
    outs = dict(zip(needs, out_refs))
    g = g_ref[...].astype(jnp.float32)                       # [m, d_blk]
    if "scores" in outs:
        outs["scores"][...] += _majority_scores(g, m)[1]
    if "l1" in outs or "d2med" in outs:
        diff = g - _median_rows(g, m)
        if "l1" in outs:
            outs["l1"][...] += jnp.sum(jnp.abs(diff), axis=1, keepdims=True)
        if "d2med" in outs:
            outs["d2med"][...] += jnp.sum(diff * diff, axis=1, keepdims=True)
    if "gram" in outs:
        outs["gram"][...] += jax.lax.dot_general(
            g, g, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def fused_stats_pallas(G, needs, d_blk: int = 2048,
                       interpret: bool = True) -> dict:
    """G [m, d] -> {stat: [m] (gram: [m, m])} for any subset of
    ref.STAT_NAMES, in ONE grid pass over G (one HBM read total,
    however many statistics the aggregator declared).

    Zero-pad columns contribute +1 per worker to ``scores`` (subtracted)
    and exactly 0 to l1/d2med/gram."""
    m = G.shape[0]
    needs = tuple(n for n in ref.STAT_NAMES if n in needs)
    G, d_blk, grid, pad = _tiling(G, d_blk)
    shapes = [(m, m) if n == "gram" else (m, 1) for n in needs]
    parts = pl.pallas_call(
        functools.partial(_fused_stats_kernel, m=m, needs=needs),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, d_blk), lambda i: (0, i))],
        out_specs=[pl.BlockSpec(s, lambda i: (0, 0)) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct(
            s, jnp.int32 if n == "scores" else jnp.float32)
            for n, s in zip(needs, shapes)],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
        name="fused_stats",
    )(G)
    out = {}
    for n, p in zip(needs, parts):
        if n != "gram":
            p = p[:, 0]
        out[n] = (p - pad).astype(jnp.float32) if n == "scores" else p
    return out


def brsgd_partials_pallas(G, d_blk: int = 2048, interpret: bool = True):
    """G: [m, d] -> (scores [m], l1 [m]) with no [d]-sized outputs —
    the fused-stats pass over exactly BrSGD's declared statistics."""
    st = fused_stats_pallas(G, ("scores", "l1"), d_blk=d_blk,
                            interpret=interpret)
    return st["scores"], st["l1"]


def _stats_kernel(g_ref, med_ref, mean_ref, score_ref, l1_ref, *, m: int):
    _zero_at_first_step((score_ref, l1_ref))
    g = g_ref[...].astype(jnp.float32)                       # [m, d_blk]
    mean_c, scores = _majority_scores(g, m)
    mean_ref[...] = mean_c
    score_ref[...] += scores
    med = _median_rows(g, m)
    med_ref[...] = med
    l1_ref[...] += jnp.sum(jnp.abs(g - med), axis=1, keepdims=True)


def brsgd_stats_pallas(G, d_blk: int = 2048, interpret: bool = True):
    """G: [m, d] -> (median [d], mean [d], scores [m], l1 [m])."""
    m, d = G.shape
    G, d_blk, grid, pad = _tiling(G, d_blk)
    row = pl.BlockSpec((1, d_blk), lambda i: (0, i))
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    med, mean, scores, l1 = pl.pallas_call(
        functools.partial(_stats_kernel, m=m),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, d_blk), lambda i: (0, i))],
        out_specs=[row, row, col, col],
        out_shape=[jax.ShapeDtypeStruct((1, G.shape[1]), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((m, 1), jnp.int32),
           jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
        name="brsgd_stats",
    )(G)
    # zero-pad columns scored 1 for every worker
    scores = (scores[:, 0] - pad).astype(jnp.float32)
    return med[0, :d], mean[0, :d], scores, l1[:, 0]


def _combine(w, g_ref):
    """Σ_i w_i g_i over the tile: (m, 1) weights against (m, d_blk)
    rows, an exact f32 multiply-add on the vector unit (an MXU dot would
    round g to bf16 at default precision) -> (1, d_blk)."""
    return jnp.sum(w * g_ref[...].astype(jnp.float32), axis=0, keepdims=True)


def _select_mean_kernel(thr_ref, sl_ref, g_ref, out_ref, w_ref):
    """C1∩C2 selection (paper Alg. 2) + masked row sum, fused.

    thr (SMEM): (kth score, 2·𝔗).  sl: [m, 2] (scores | l1).
    Recomputing the [m]-sized selection per grid step costs nothing next
    to the (m, d_blk) tile load and keeps the whole second phase in one
    kernel."""
    c2 = sl_ref[:, 0:1] >= thr_ref[0]
    c1 = sl_ref[:, 1:2] <= thr_ref[1]
    both = jnp.where(c1 & c2, 1.0, 0.0)                      # [m, 1]
    # C1∩C2 empty -> fall back to C2
    w = jnp.where(jnp.max(both, axis=0, keepdims=True) > 0, both,
                  jnp.where(c2, 1.0, 0.0))
    w_ref[...] = w
    out_ref[...] = _combine(w, g_ref)


def select_mean_pallas(G, scores, l1, beta: float, threshold,
                       d_blk: int = 2048, interpret: bool = True):
    """Fused second pass of local BrSGD: selection + masked mean.

    Returns (aggregate [d], selection weights [m]).  Selection semantics
    are identical to ``engine.brsgd_select`` (same IEEE comparisons on
    the same inputs)."""
    m, d = G.shape
    G, d_blk, grid, _pad = _tiling(G, d_blk)   # zero pad adds 0 to Σ w g
    kth, T = ref.brsgd_thresholds(scores, l1, beta, threshold)
    sl = jnp.stack([scores, l1], axis=1).astype(jnp.float32)  # [m, 2]
    thr = jnp.stack([kth, 2.0 * T]).astype(jnp.float32)       # [2]
    acc, w = pl.pallas_call(
        _select_mean_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((m, 2), lambda i: (0, 0)),
                  pl.BlockSpec((m, d_blk), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, d_blk), lambda i: (0, i)),
                   pl.BlockSpec((m, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, G.shape[1]), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
        name="select_mean",
    )(thr, sl, G)
    w = w[:, 0]
    sw = jnp.sum(w)
    return acc[0, :d] / jnp.where(sw > 0, sw, 1.0), w


def _masked_mean_kernel(w_ref, g_ref, out_ref):
    out_ref[...] = _combine(w_ref[...], g_ref)


def masked_mean_pallas(G, mask, d_blk: int = 2048, interpret: bool = True):
    """Mean over selected rows.  mask: [m] bool, or f32 weights (the
    engine's weighted combine) — the denominator is Σw, guarded to 1
    when the mask is empty."""
    m, d = G.shape
    G, d_blk, grid, _pad = _tiling(G, d_blk)
    w = mask.astype(jnp.float32)
    out = pl.pallas_call(
        _masked_mean_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, 1), lambda i: (0, 0)),
                  pl.BlockSpec((m, d_blk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, d_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, G.shape[1]), jnp.float32),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="masked_mean",
    )(w[:, None], G)
    sw = jnp.sum(w)
    return out[0, :d] / jnp.where(sw > 0, sw, 1.0)


def _order_stat_kernel(g_ref, out_ref, *, m: int, lo: int, hi: int):
    """Mean of the sorted rows lo..hi-1 — the median (one or the two
    middle rows) or the trimmed mean."""
    rows = _sorted_rows(g_ref[...].astype(jnp.float32), m)
    acc = rows[lo]
    for i in range(lo + 1, hi):
        acc = acc + rows[i]
    out_ref[...] = acc if hi - lo == 1 else acc / (hi - lo)


def _order_stat_pallas(G, lo: int, hi: int, d_blk: int, interpret: bool,
                       name: str):
    m, d = G.shape
    G, d_blk, grid, _pad = _tiling(G, d_blk)   # zero columns -> 0, sliced off
    out = pl.pallas_call(
        functools.partial(_order_stat_kernel, m=m, lo=lo, hi=hi),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, d_blk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, d_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, G.shape[1]), jnp.float32),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name=name,
    )(G)
    return out[0, :d]


def cwise_median_pallas(G, d_blk: int = 2048, interpret: bool = True):
    """Coordinate-wise median baseline (same bitonic machinery); the
    two-middle average divides by 2 exactly."""
    m = G.shape[0]
    lo = (m - 1) // 2
    return _order_stat_pallas(G, lo, m - lo, d_blk, interpret,
                              "cwise_median")


def trimmed_mean_pallas(G, trim_frac: float, d_blk: int = 2048,
                        interpret: bool = True):
    """Coordinate-wise trimmed mean (Yin et al. 2018): drop the k
    smallest and k largest per dimension, k = ⌊trim_frac·m⌋."""
    m = G.shape[0]
    k = ref.trim_k(trim_frac, m)        # shared degenerate-trim guard
    return _order_stat_pallas(G, k, m - k, d_blk, interpret,
                              "trimmed_mean")
