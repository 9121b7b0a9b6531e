"""Plain BrSGD (paper Algorithm 2) over a worker-major matrix G [m, d],
in float32 and in column blocks, so that it runs beside a G that fills
most of a chip.

Per column: the column mean splits the workers into those at or above
it and those below; the larger side is the majority (a tie at exactly
m/2 counts for the side at or above the mean).  A worker's score is the
number of columns in which it is in the majority (Constraint 2); its l1
is the sum over columns of its distance to the coordinate-wise median
(the mean of the two middle values for even m).  The kept set is the
workers whose score is at least the (m - ⌈βm⌉)-th smallest score and
whose l1 is at most twice 𝔗, where 𝔗 is the given threshold, or, when
that is 0, the lower quartile of the l1 values by nearest rank (index
q·(m-1), halves rounded down).  If no worker passes both, the score
filter alone decides.  The aggregate is the mean of the kept rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 22


def _blocks(d: int, block: int):
    for s in range(0, d, block):
        yield s, min(block, d - s)


def _as_f32(g, quant):
    """float32 values of a block; with ``quant``, first rounded to that
    dtype under one scale per row of the block (the largest magnitude
    maps to the dtype's largest finite value)."""
    g = g.astype(jnp.float32)
    if quant is None:
        return g
    big = float(jnp.finfo(quant).max)
    scale = jnp.max(jnp.abs(g), axis=1, keepdims=True) / big
    scale = jnp.where(scale > 0, scale, 1.0)
    return (g / scale).astype(quant).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("size", "quant"))
def _block_stats(G, start, size: int, quant=None):
    m = G.shape[0]
    g = _as_f32(jax.lax.dynamic_slice_in_dim(G, start, size, axis=1), quant)
    mean = jnp.sum(g, axis=0) / m
    above = g >= mean
    n_above = jnp.sum(above.astype(jnp.int32), axis=0)
    majority = jnp.where(2 * n_above >= m, above, ~above)
    scores = jnp.sum(majority.astype(jnp.int32), axis=1)
    srt = jnp.sort(g, axis=0)
    med = (srt[(m - 1) // 2] + srt[m // 2]) / 2
    l1 = jnp.sum(jnp.abs(g - med), axis=1)
    return scores, l1


def stats(G, quant=None, block: int = BLOCK):
    """(scores int64 [m], l1 float32 [m]) on the host.  ``quant`` rounds
    G to that dtype first, one scale per row of each block (the control of
    a lower precision)."""
    m, d = G.shape
    scores = np.zeros(m, np.int64)
    l1 = np.zeros(m, np.float32)
    for s, n in _blocks(d, block):
        sc, l = _block_stats(G, s, n, quant)
        scores += np.asarray(sc, np.int64)
        l1 += np.asarray(l, np.float32)
    return scores, l1


def lower_quartile_index(m: int) -> int:
    virt = 0.25 * (m - 1)
    low = math.floor(virt)
    return low if virt - low <= 0.5 else low + 1


def select(scores, l1, beta: float, threshold: float = 0.0):
    """[m] bool: the kept workers."""
    m = len(scores)
    k = max(1, math.ceil(beta * m))
    kth = np.sort(scores)[m - k]
    l1 = np.asarray(l1, np.float32)
    T = (np.float32(threshold) if threshold > 0
         else np.sort(l1)[lower_quartile_index(m)])
    c1 = l1 <= np.float32(2) * T
    c2 = np.asarray(scores) >= kth
    kept = c1 & c2
    return kept if kept.any() else c2


@functools.partial(jax.jit, static_argnames=("size", "quant"))
def _block_mean(G, w, start, size: int, quant=None):
    g = _as_f32(jax.lax.dynamic_slice_in_dim(G, start, size, axis=1), quant)
    acc = jnp.zeros((size,), jnp.float32)
    for i in range(G.shape[0]):
        acc = acc + w[i] * g[i]
    return acc / jnp.sum(w)


@functools.partial(jax.jit, static_argnames=("size", "quant"))
def _block_gap(G, w, out, start, size: int, quant=None):
    ref = _block_mean(G, w, start, size, quant)
    got = jax.lax.dynamic_slice_in_dim(out, start, size)
    gap = jnp.abs(got.astype(jnp.float32) - ref)
    return jnp.max(jnp.where(jnp.isnan(gap), jnp.inf, gap))


@functools.partial(jax.jit, static_argnames=("size", "quant"))
def _block_pair_gap(G, w, w_q, start, size: int, quant):
    ref = _block_mean(G, w, start, size)
    low = _block_mean(G, w_q, start, size, quant)
    gap = jnp.abs(low - ref)
    return jnp.max(jnp.where(jnp.isnan(gap), jnp.inf, gap))


def control_gap(G, kept, kept_q, quant, block: int = BLOCK) -> float:
    """max |aggregate of G rounded to ``quant`` (kept rows ``kept_q``) -
    aggregate of G (kept rows ``kept``)| over every column."""
    w = jnp.asarray(np.asarray(kept, np.float32))
    w_q = jnp.asarray(np.asarray(kept_q, np.float32))
    gap = 0.0
    for s, n in _blocks(G.shape[1], block):
        gap = max(gap, float(_block_pair_gap(G, w, w_q, s, n, quant)))
    return gap


def max_gap(G, kept, out, quant=None, block: int = BLOCK) -> float:
    """max |out - mean of the kept rows| over every column (inf where out
    holds a NaN)."""
    w = jnp.asarray(np.asarray(kept, np.float32))
    gap = 0.0
    for s, n in _blocks(G.shape[1], block):
        gap = max(gap, float(_block_gap(G, w, out, s, n, quant)))
    return gap
