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

Tiling (the TPU's (8, 128) rule): grid over d; each step DMAs an
(m, block) tile of G into VMEM — m <= 64 workers is a compile-time
constant and the block's full first dim.  A grid step has a fixed cost
of about 0.35 µs whatever it moves, so the block follows the shape
(``_tiling``): about 1 MiB of G (65,536 columns at m = 8 bf16), capped
so that the kernel's double-buffered tile and [d]-sized outputs fit a
12 MiB VMEM budget, in whole inner chunks.  G is never copied or
padded: the grid is cdiv(d, block), and a leaf narrower than one block
is one block of d rounded up to 1,024 columns.  The last block is then
ragged: its buffer holds garbage past d.  Results there fall outside
the [d] outputs, which the TPU writes back only in bounds; before the
statistics accumulate, the kernel zeroes the garbage and the majority
score masks those columns by their index, so they add 0 to every
statistic (interpret mode pads the same columns with NaN).

Inside a block the kernel loops over chunks of up to 4,096 columns and
reshapes each into m dense worker rows: row i holds worker i's columns,
one (8, 128) vector register per 1,024 of them.  Every per-column
statistic is then a handful of whole-register ops per 1,024 columns:
sums over workers run in row order as the jnp reference's, the median's
bitonic network is jnp.minimum/maximum between rows (MXU-free), and
per-worker statistics accumulate in one register per worker until the
block ends, when they are summed into [m, 1] column outputs (Gram:
[m, m]).  Those stay resident across the grid — the grid axis is
``"arbitrary"`` and step 0 zeroes them.  [d]-sized outputs are 1-D
blocks of the [d] result, stored a chunk at a time.  Row combines are
exact f32 multiply-adds of (m, 1) weights, divided by Σw in the kernel;
scalar thresholds ride in SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

_LANES = 128
_SUBLANES = 8
_BLOCK_BYTES = 1 << 20      # G per grid step: ~1.3 µs of HBM time
_VMEM_BUDGET = 12 << 20     # of v5e's 16 MiB default scoped VMEM
_CHUNK = 4096               # columns per step of a block's inner loop
_ROW_TILE = _SUBLANES * _LANES  # columns of one dense worker row
# resident [m]-sized accumulators need the grid run in order
_ACCUMULATE = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _tiling(G, d_blk: int | None, row_outs: int):
    """Tile G [m, d]'s dim axis -> (block, grid, chunk).

    The block is ``_BLOCK_BYTES`` of G, capped so that the kernel's VMEM
    need — the double-buffered (m, block) tile, its rows padded to whole
    (32 B, 128) tiles, and ``row_outs`` double-buffered [block] f32
    outputs — stays within ``_VMEM_BUDGET``, and rounded down to whole
    chunks; ``d_blk`` overrides it (tests force many blocks and ragged
    tails with it).  Every block is a whole number of 1,024-column
    groups, and no wider than d rounded up to them, so a leaf narrower
    than one block is one block.  The chunk is the largest divisor of
    the block up to ``_CHUNK`` columns.  The grid is cdiv(d, block): G
    is not padded, and the last block is ragged where d is no whole
    number of blocks."""
    m, d = G.shape
    item = G.dtype.itemsize
    if d_blk is None:
        rows = -(-m * item // 32) * 32 // item
        per_col = 2 * (rows * item + row_outs * 4)
        cols = min(_BLOCK_BYTES // (m * item), _VMEM_BUDGET // per_col)
        block = max(_CHUNK, cols // _CHUNK * _CHUNK)
    else:
        block = -(-d_blk // _ROW_TILE) * _ROW_TILE
    block = min(block, -(-d // _ROW_TILE) * _ROW_TILE)
    return block, -(-d // block), math.gcd(block, _CHUNK)


def _each_chunk(body, carry, valid: int, block: int, chunk: int):
    """``carry = body(start, limit, carry)`` over the chunks of this grid
    step's block that hold columns of G: ``start`` the chunk's first
    column in the block, ``limit`` None where every chunk is whole, else
    its number of columns of G (it may exceed the chunk).  The last
    block holds ``valid`` columns of G and loops over just the chunks
    they reach."""
    n = block // chunk
    ragged = valid % chunk != 0
    if valid < block:
        last = pl.program_id(0) == pl.num_programs(0) - 1
        n = jnp.where(last, -(-valid // chunk), n)
        room = jnp.where(last, valid, block)

    def step(c, carry):
        start = pl.multiple_of(c * chunk, chunk)
        return body(start, room - start if ragged else None, carry)

    return lax.fori_loop(0, n, step, carry)


# ---- worker rows -----------------------------------------------------------
#
# Every statistic is written once, over a list of m worker rows.  A chunk
# gives dense rows of (8, 128) registers (row i holds worker i's columns,
# sublane t of a register the t-th 128 of its 1,024), so a sum over
# workers or a compare-exchange of the sorting network is one vector op
# per 1,024 columns.

def _dense_rows(g, m: int):
    """The f32 chunk g [m, w] (w a multiple of 1,024) as m dense worker
    rows of shape (w / 1024, 8, 128): one register per 1,024 columns."""
    x = g.reshape(m, g.shape[1] // _ROW_TILE, _SUBLANES, _LANES)
    return [x[i] for i in range(m)]


def _columns(shape):
    """Each element's column within its chunk, for dense rows."""
    k, s, l = (lax.broadcasted_iota(jnp.int32, shape, i) for i in range(3))
    return (k * _SUBLANES + s) * _LANES + l


def _row_sum(rows):
    """Σ of the rows in row order, ((r_0 + r_1) + r_2) + …, as the jnp
    reference sums."""
    acc = rows[0]
    for r in rows[1:]:
        acc = lax.add(acc, r)
    return acc


def _sorted_rows(rows):
    """The rows sorted elementwise ascending, padded to a power of two
    with +inf rows that sort last — via the static bitonic network the
    jnp reference path runs (ref.bitonic_stages is the one copy)."""
    m = len(rows)
    mp = 1 << max(1, math.ceil(math.log2(m)))
    rows = list(rows) + [lax.full_like(rows[0], jnp.inf)] * (mp - m)
    for stage in ref.bitonic_stages(mp):
        for i, l, asc in stage:
            lo = lax.min(rows[i], rows[l])
            hi = lax.max(rows[i], rows[l])
            rows[i], rows[l] = (lo, hi) if asc else (hi, lo)
    return rows


def _stats(rows, needs, live=None):
    """(column mean, median, {stat: per-worker contributions}) of the
    worker rows for needs ⊆ {scores, l1, d2med}.  Majority scores: the
    column mean splits the workers; 1 for each worker on the larger side
    (a tie at m/2 counts for the side at or above the mean).  Distances
    run to the coordinate-wise median, the mean of the two middle rows
    for even m.  A column outside ``live`` (zeros) scores no one.  (lax,
    not jnp: the kernels trace these for every shape, and set-up time
    counts.)"""
    m = len(rows)
    const = functools.partial(lax.full_like, rows[0])
    mean = med = None
    parts = {}
    if "scores" in needs:
        # Σ/m; for a power of two the product by 1/m has the same bits
        total = _row_sum(rows)
        mean = (lax.mul(total, const(1.0 / m)) if m & (m - 1) == 0
                else lax.div(total, const(float(m))))
        above = [lax.ge(r, mean) for r in rows]
        one, zero = const(1.0), const(0.0)
        count = _row_sum([lax.select(a, one, zero) for a in above])
        major = lax.ge(count, const(m / 2))
        if live is not None:     # zeros: everyone is above, no one major
            major = lax.bitwise_and(major, live)
        parts["scores"] = [lax.select(lax.eq(a, major), one, zero)
                           for a in above]
    if "l1" in needs or "d2med" in needs:
        srt = _sorted_rows(rows)
        med = srt[(m - 1) // 2]
        if m % 2 == 0:
            med = lax.mul(lax.add(med, srt[m // 2]), const(0.5))
        diff = [lax.sub(r, med) for r in rows]
        if "l1" in needs:
            parts["l1"] = [lax.abs(x) for x in diff]
        if "d2med" in needs:
            parts["d2med"] = [lax.mul(x, x) for x in diff]
    return mean, med, parts


def _splat(x, shape):
    """A (1, 1) value as an array of ``shape`` (Mosaic broadcasts lanes
    and sublanes in separate steps)."""
    return jnp.broadcast_to(jnp.broadcast_to(x, (1, shape[-1])), shape)


def _block_pass(g_ref, fn, *, m: int, d: int, block: int, chunk: int,
                row_refs=(), acc_refs=(), finish=lambda v: v,
                weights=None, on_chunk=None):
    """Runs ``fn`` over this grid step's chunks of G.

    ``fn(rows, live)`` takes the m dense worker rows of a chunk and
    returns (results, parts): results, per-column values of the rows'
    shape, stored through ``finish`` into the [d] ``row_refs``; parts,
    per-stat lists of m per-worker contributions, summed into one
    (8, 128) register per worker and statistic until the block ends and
    then added to the (m, 1) ``acc_refs``.  ``live`` marks the columns
    of G (None: all of them).  Past d, a ragged last block's buffer
    holds garbage: results there fall outside [d] and are not written
    back, and where there are accumulators or ``on_chunk`` the garbage
    is zeroed first, so that it adds 0 to sums of rows, distances and
    the Gram matrix; ``fn`` masks the rest with ``live``.  ``weights``
    (m, 1) scale the workers' rows first; ``on_chunk(g)`` sees each f32
    chunk g [m, chunk]."""
    grid = -(-d // block)
    valid = d - (grid - 1) * block
    end = -(-valid // chunk) * chunk
    if (acc_refs or on_chunk is not None) and valid < end:
        @pl.when(pl.program_id(0) == grid - 1)
        def _():
            g_ref[:, valid:end] = jnp.zeros((m, end - valid), g_ref.dtype)

    def body(start, limit, acc):
        g = g_ref[:, pl.ds(start, chunk)].astype(jnp.float32)
        if weights is not None:
            g = weights * g
        if on_chunk is not None:
            on_chunk(g)
        rows = _dense_rows(g, m)
        live = None if limit is None else _columns(rows[0].shape) < limit
        res, parts = fn(rows, live)
        for r, v in zip(row_refs, res):
            r[pl.ds(start, chunk)] = finish(v).reshape(chunk)
        return [[a + jnp.sum(p, axis=0) for a, p in zip(acc_i, p_i)]
                for acc_i, p_i in zip(acc, parts)]

    zero = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
    acc = _each_chunk(body, [[zero] * m for _ in acc_refs], valid, block,
                      chunk)
    for r, a in zip(acc_refs, acc):
        # back in column order and summed as the jnp reference sums a
        # row (the same sum, for a G of under 1,024 columns)
        rows = jnp.stack(a).reshape(m, _ROW_TILE)[:, :min(d, _ROW_TILE)]
        r[...] += jnp.sum(rows, axis=1, keepdims=True).astype(r.dtype)


def _zero_at_first_step(refs):
    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in refs:
            r[...] = jnp.zeros(r.shape, r.dtype)


def _fused_stats_kernel(g_ref, *out_refs, m: int, needs: tuple, d: int,
                        block: int, chunk: int):
    """One block accumulating the requested subset of ref.STAT_NAMES
    into its resident outputs.

    ``needs`` is a canonical-order tuple matching ``out_refs``.  A
    chunk's coordinate-wise median is computed at most once and shared
    by l1/d2med; the Gram partial is the chunk's g @ gᵀ.  Score counts
    are f32 sums of 0/1 (exact: a block has far fewer than 2^24 columns)
    and leave each block as int32, so the count accumulated across the
    grid stays exact at any leaf width — an f32 running sum past 2^24
    drops counts and can flip BrSGD's kth-score cut."""
    _zero_at_first_step(out_refs)
    outs = dict(zip(needs, out_refs))
    vec = [n for n in needs if n != "gram"]

    def gram(g):
        outs["gram"][...] += lax.dot_general(
            g, g, (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def fn(rows, live):
        parts = _stats(rows, vec, live)[2]
        return (), [parts[n] for n in vec]

    _block_pass(g_ref, fn, m=m, d=d, block=block, chunk=chunk,
                acc_refs=[outs[n] for n in vec],
                on_chunk=gram if "gram" in outs else None)


def fused_stats_pallas(G, needs, d_blk: int | None = None,
                       interpret: bool = True) -> dict:
    """G [m, d] -> {stat: [m] (gram: [m, m])} for any subset of
    ref.STAT_NAMES, in ONE grid pass over G (one HBM read total,
    however many statistics the aggregator declared)."""
    m, d = G.shape
    needs = tuple(n for n in ref.STAT_NAMES if n in needs)
    block, grid, chunk = _tiling(G, d_blk, 0)
    shapes = [(m, m) if n == "gram" else (m, 1) for n in needs]
    parts = pl.pallas_call(
        functools.partial(_fused_stats_kernel, m=m, needs=needs, d=d,
                          block=block, chunk=chunk),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
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
        out[n] = p.astype(jnp.float32) if n == "scores" else p
    return out


def brsgd_partials_pallas(G, d_blk: int | None = None,
                          interpret: bool = True):
    """G: [m, d] -> (scores [m], l1 [m]) with no [d]-sized outputs —
    the fused-stats pass over exactly BrSGD's declared statistics."""
    st = fused_stats_pallas(G, ("scores", "l1"), d_blk=d_blk,
                            interpret=interpret)
    return st["scores"], st["l1"]


def _row_spec(block: int):
    """A [block] slice of a [d] f32 output: the caller gets [d] with no
    reshape or slice."""
    return pl.BlockSpec((block,), lambda i: (i,))


def _stats_kernel(g_ref, med_ref, mean_ref, score_ref, l1_ref, *, m: int,
                  d: int, block: int, chunk: int):
    _zero_at_first_step((score_ref, l1_ref))

    def fn(rows, live):
        mean, med, parts = _stats(rows, ("scores", "l1"), live)
        return (med, mean), [parts["scores"], parts["l1"]]

    _block_pass(g_ref, fn, m=m, d=d, block=block, chunk=chunk,
                row_refs=(med_ref, mean_ref), acc_refs=(score_ref, l1_ref))


def brsgd_stats_pallas(G, d_blk: int | None = None, interpret: bool = True):
    """G: [m, d] -> (median [d], mean [d], scores [m], l1 [m])."""
    m, d = G.shape
    block, grid, chunk = _tiling(G, d_blk, 2)
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    med, mean, scores, l1 = pl.pallas_call(
        functools.partial(_stats_kernel, m=m, d=d, block=block, chunk=chunk),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=[_row_spec(block), _row_spec(block), col, col],
        out_shape=[jax.ShapeDtypeStruct((d,), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((m, 1), jnp.int32),
           jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
        name="brsgd_stats",
    )(G)
    return med, mean, scores[:, 0].astype(jnp.float32), l1[:, 0]


def _weighted_mean(w, g_ref, out_ref, *, m: int, d: int, block: int,
                   chunk: int):
    """out = Σ_i w_i g_i / Σ_i w_i: (m, 1) weights against the f32 rows,
    exact f32 multiply-adds on the vector unit (an MXU dot would round g
    to bf16 at default precision), then the IEEE division by Σw, guarded
    to 1 when no row is weighted."""
    sw = jnp.sum(w, axis=0, keepdims=True)                   # (1, 1)
    den = jnp.where(sw > 0, sw, 1.0)
    _block_pass(g_ref, lambda rows, live: ((_row_sum(rows),), ()), m=m, d=d,
                block=block, chunk=chunk, row_refs=(out_ref,), weights=w,
                finish=lambda v: v / _splat(den, v.shape))


def _rank_select(x, k: int):
    """ref.rank_select on a column x (m, 1): the k-th smallest value,
    (1, 1), found by counting ranks with each element spread over the
    column in turn."""
    m = x.shape[0]
    lt = le = jnp.zeros(x.shape, jnp.int32)
    for j in range(m):
        xj = jnp.broadcast_to(x[j:j + 1], x.shape)
        lt = lt + jnp.where(xj < x, 1, 0)
        le = le + jnp.where(xj <= x, 1, 0)
    hit = (lt <= k) & (k < le)
    return jnp.max(jnp.where(hit, x, -jnp.inf), axis=0, keepdims=True)


def _select_mean_kernel(thr_ref, s_ref, l_ref, g_ref, out_ref, w_ref, *,
                        m: int, kth: int, quartile: int, d: int, block: int,
                        chunk: int):
    """C1∩C2 selection (paper Alg. 2) + masked row mean, fused.

    thr (SMEM): the given threshold 𝔗 (<= 0: the l1 quartile).  s, l:
    (m, 1) scores and l1.  The selection is made at the first grid step,
    with ref.brsgd_select_mask's comparisons, and kept in the resident
    weights output."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        s, l = s_ref[...], l_ref[...]
        T = jnp.where(thr_ref[0] > 0, thr_ref[0], _rank_select(l, quartile))
        c2 = s >= _rank_select(s, kth)
        c1 = l <= 2.0 * T
        both = jnp.where(c1 & c2, 1.0, 0.0)
        # C1∩C2 empty -> fall back to C2
        w_ref[...] = jnp.where(jnp.max(both, axis=0, keepdims=True) > 0,
                               both, jnp.where(c2, 1.0, 0.0))

    _weighted_mean(w_ref[...], g_ref, out_ref, m=m, d=d, block=block,
                   chunk=chunk)


def select_mean_pallas(G, scores, l1, beta: float, threshold,
                       d_blk: int | None = None, interpret: bool = True):
    """Fused second pass of local BrSGD: selection + masked mean.

    Returns (aggregate [d], selection weights [m]).  Selection semantics
    are identical to ``engine.brsgd_select`` (ref.brsgd_thresholds'
    counting quantiles and the same IEEE comparisons on the same
    inputs), and nothing runs between the two passes."""
    m, d = G.shape
    block, grid, chunk = _tiling(G, d_blk, 1)
    col = pl.BlockSpec((m, 1), lambda i: (0, 0))
    agg, w = pl.pallas_call(
        functools.partial(
            _select_mean_kernel, m=m, kth=m - max(1, math.ceil(beta * m)),
            quartile=ref.quantile_nearest_index(0.25, m), d=d, block=block,
            chunk=chunk),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), col, col,
                  pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=[_row_spec(block), col],
        out_shape=[jax.ShapeDtypeStruct((d,), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
        name="select_mean",
    )(jnp.reshape(jnp.asarray(threshold, jnp.float32), (1,)),
      scores.astype(jnp.float32)[:, None], l1.astype(jnp.float32)[:, None],
      G)
    return agg, w[:, 0]


def _masked_mean_kernel(w_ref, g_ref, out_ref, **kw):
    _weighted_mean(w_ref[...], g_ref, out_ref, **kw)


def masked_mean_pallas(G, mask, d_blk: int | None = None,
                       interpret: bool = True):
    """Mean over selected rows.  mask: [m] bool, or f32 weights (the
    engine's weighted combine) — the denominator is Σw, guarded to 1
    when the mask is empty."""
    m, d = G.shape
    block, grid, chunk = _tiling(G, d_blk, 1)
    w = mask.astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_masked_mean_kernel, m=m, d=d, block=block,
                          chunk=chunk),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, 1), lambda i: (0, 0)),
                  pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=_row_spec(block),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="masked_mean",
    )(w[:, None], G)


def _order_stat_kernel(g_ref, out_ref, *, m: int, lo: int, hi: int, d: int,
                       block: int, chunk: int):
    """Mean of the sorted rows lo..hi-1 — the median (one or the two
    middle rows) or the trimmed mean."""
    def fn(rows, live):
        return (_row_sum(_sorted_rows(rows)[lo:hi]),), ()

    _block_pass(g_ref, fn, m=m, d=d, block=block, chunk=chunk,
                row_refs=(out_ref,),
                finish=lambda v: v if hi - lo == 1 else v / (hi - lo))


def _order_stat_pallas(G, lo: int, hi: int, d_blk: int | None,
                       interpret: bool, name: str):
    m, d = G.shape
    block, grid, chunk = _tiling(G, d_blk, 1)
    return pl.pallas_call(
        functools.partial(_order_stat_kernel, m=m, lo=lo, hi=hi, d=d,
                          block=block, chunk=chunk),
        grid=(grid,),
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=_row_spec(block),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        compiler_params=_PARALLEL,
        interpret=interpret,
        name=name,
    )(G)


def cwise_median_pallas(G, d_blk: int | None = None, interpret: bool = True):
    """Coordinate-wise median baseline (same sorting network); the
    two-middle average divides by 2 exactly."""
    m = G.shape[0]
    lo = (m - 1) // 2
    return _order_stat_pallas(G, lo, m - lo, d_blk, interpret,
                              "cwise_median")


def trimmed_mean_pallas(G, trim_frac: float, d_blk: int | None = None,
                        interpret: bool = True):
    """Coordinate-wise trimmed mean (Yin et al. 2018): drop the k
    smallest and k largest per dimension, k = ⌊trim_frac·m⌋."""
    m = G.shape[0]
    k = ref.trim_k(trim_frac, m)        # shared degenerate-trim guard
    return _order_stat_pallas(G, k, m - k, d_blk, interpret,
                              "trimmed_mean")
