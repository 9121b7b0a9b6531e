"""Pure-jnp oracles for the aggregation kernels.

These are the ground truth the Pallas kernels are validated against and
the fallback implementation on non-TPU backends.  All operate on the
gradient matrix ``G`` of shape [m, d] (m workers, d dimensions).

Determinism note: ``column_mean_ref``/``masked_mean_det`` accumulate
rows in a fixed sequential order (row 0, 1, …, m-1) and divide behind
an optimization barrier.  Rationale: XLA is free to reassociate plain
reduce-sums and to fold a constant divisor into a multiply-by-
reciprocal; both perturb the result by ~1 ulp, which is a relative
error of ~1e-4 on near-zero coordinates and broke the seed's
mean-equivalence tests.  The sequential order matches NumPy's
``np.add.reduce`` along axis 0, so ``mean`` is bit-identical to
``np.mean(G, axis=0)`` and ``masked_mean_det`` with a full mask is
bit-identical to ``mean``.  ``masked_mean_ref`` keeps the matvec form:
it is the oracle for the (blockwise-accumulating) Pallas kernel, which
is validated against it under tolerance.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Canonical names of the additive per-leaf aggregation statistics (the
# engine registry re-exports this; it lives here so the kernel layer can
# share it without a circular import).  Order is the canonical emission
# order of the fused-stats pass.
STAT_NAMES = ("scores", "l1", "d2med", "gram")


# ---------------------------------------------------------------------------
# one-sort contract: the shared sorted-rows pass (DESIGN.md §Perf)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def bitonic_stages(n: int):
    """Compare-exchange index pairs for a bitonic sorting network of
    size n (a power of two): tuple of stages, each a tuple of
    (i, j, ascending) pairs.  Shared by the jnp reference sort below and
    the Pallas kernels (kernels/brsgd_stats.py)."""
    stages = []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            pairs = []
            for i in range(n):
                l = i ^ j
                if l > i:
                    pairs.append((i, l, (i & k) == 0))
            stages.append(tuple(pairs))
            j //= 2
        k *= 2
    return tuple(stages)


def sorted_worker_rows(G, axis: int = 0):
    """Worker slices of G sorted ascending along ``axis`` — a list of m
    arrays (f32, ``axis`` removed), via a static bitonic network of
    vectorized jnp.minimum/maximum stages.

    This is THE sort of the one-sort contract (DESIGN.md §Perf): every
    order statistic the reference path needs (coordinate-wise median,
    l1/d2med distances to it, trimmed mean) derives from this one pass.
    XLA lowers its CPU ``sort`` to scalar loops — at [8, 160k] the
    network is >100x faster and bit-identical on finite inputs (min/max
    networks don't totally order NaNs; callers assume finite data, as
    the Pallas kernels already do).  The worker count is a compile-time
    constant, so the network fully unrolls (O(m log^2 m) vector ops).
    """
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)
    m = x.shape[0]
    mp = 1 << max(1, math.ceil(math.log2(m)))
    rows = [x[i] for i in range(m)]
    rows += [jnp.full_like(rows[0], jnp.inf)] * (mp - m)   # pad sorts last
    for stage in bitonic_stages(mp):
        for i, l, asc in stage:
            lo = jnp.minimum(rows[i], rows[l])
            hi = jnp.maximum(rows[i], rows[l])
            rows[i], rows[l] = (lo, hi) if asc else (hi, lo)
    return rows[:m]


def median_from_sorted(rows):
    """Coordinate-wise median from :func:`sorted_worker_rows` output —
    identical to jnp.median on finite inputs (the two-middle average
    divides by 2 exactly)."""
    m = len(rows)
    if m % 2:
        return rows[m // 2]
    return 0.5 * (rows[m // 2 - 1] + rows[m // 2])


def sorted_worker_stack(G, axis: int = 0):
    """Full ascending sort along ``axis`` as one stacked [m, ...] array,
    running each bitonic stage as ONE vectorized permute+min+max+select
    over the whole stack.

    Complements :func:`sorted_worker_rows` for consumers that read MANY
    sorted rows (trimmed mean at larger m): the per-row network relies
    on XLA dead-code elimination, and XLA's CPU fusion re-computes the
    surviving compare-exchange cone once per consumer — O(m) duplication
    when all m rows are read.  Here every stage has a single
    producer-consumer edge, so the work stays O(m log² m) passes."""
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)
    m = x.shape[0]
    mp = 1 << max(1, math.ceil(math.log2(m)))
    if mp > m:
        pad = jnp.full((mp - m,) + x.shape[1:], jnp.inf, jnp.float32)
        x = jnp.concatenate([x, pad], axis=0)
    bshape = (mp,) + (1,) * (x.ndim - 1)
    for stage in bitonic_stages(mp):
        perm = np.arange(mp)
        keep_lo = np.zeros(mp, bool)
        for i, l, asc in stage:
            perm[i], perm[l] = l, i
            keep_lo[i], keep_lo[l] = asc, not asc
        partner = x[jnp.asarray(perm)]
        lo = jnp.minimum(x, partner)
        hi = jnp.maximum(x, partner)
        x = jnp.where(jnp.asarray(keep_lo).reshape(bshape), lo, hi)
    return x[:m]


def det_sum_rows(G):
    """Sequential f32 row sum (axis 0) — deterministic accumulation
    order, bit-identical to NumPy's np.add.reduce(G, axis=0)."""
    s, _ = jax.lax.scan(lambda c, r: (c + r, None), jnp.zeros_like(G[0]), G)
    return s


def _exact_div(x, den):
    # the barrier stops XLA constant-folding the divisor into a
    # multiply-by-reciprocal (which is ~1 ulp off true IEEE division)
    return x / jax.lax.optimization_barrier(den)


def column_mean_ref(G):
    Gf = G.astype(jnp.float32)
    return _exact_div(det_sum_rows(Gf), jnp.float32(Gf.shape[0]))


def cwise_median_ref(G, axis: int = 0):
    """Coordinate-wise median over workers (along ``axis``) — one
    bitonic sorted-rows pass, not an XLA sort."""
    return median_from_sorted(sorted_worker_rows(G, axis))


def fused_stats_ref(G, needs, axis: int = 0) -> dict:
    """One-pass fused statistics: any subset of :data:`STAT_NAMES` from
    a single shared sorted-rows pass (jnp reference of ops.fused_stats).

    G's ``axis`` indexes the m workers; every other dimension is reduced
    (the partials are additive over disjoint dimension ranges, so views
    over dim ranges sum — the ``engine.leaf_stats`` contract).  N-D
    views (blocked scope: worker axis mid-leaf) never reshape across the
    non-worker dims — only ``axis`` is moved, never merged.  The
    coordinate-wise median is computed at most once and shared by
    ``l1`` and ``d2med``; before this pass existed each statistic
    re-sorted G independently.

    The [d]-sized invariants (column mean, majority mask, median) are
    consumed through ``lax.scan``/``lax.map`` bodies rather than
    broadcast expressions: a loop body is a separate XLA computation, so
    the invariant is materialized ONCE.  Fusing the broadcast instead
    lets XLA's CPU fusion re-compute the whole producer (including the
    sort network) per worker row — measured ~m× the work at m = 64
    (DESIGN.md §Perf).
    """
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)         # [m, ...]
    m = x.shape[0]
    out = {}
    if "scores" in needs:
        mean_c = jnp.mean(x, axis=0)
        n_above, _ = jax.lax.scan(
            lambda c, g: (c + (g >= mean_c).astype(jnp.int32), None),
            jnp.zeros(x.shape[1:], jnp.int32), x)
        majority_is_above = n_above * 2 >= m
        # integer counts: an f32 sum of 0/1 is inexact past 2^24 columns
        out["scores"] = jax.lax.map(
            lambda g: jnp.sum(jnp.where(majority_is_above, g >= mean_c,
                                        g < mean_c), dtype=jnp.int32)
            .astype(jnp.float32), x)
    if "l1" in needs or "d2med" in needs:
        med = median_from_sorted(sorted_worker_rows(x))
        def dists(g):
            diff = g - med
            return jnp.sum(jnp.abs(diff)), jnp.sum(diff * diff)
        l1, d2med = jax.lax.map(dists, x)
        if "l1" in needs:
            out["l1"] = l1
        if "d2med" in needs:
            out["d2med"] = d2med
    if "gram" in needs:
        # contract every non-worker dim: G @ G.T without reshaping the
        # leaf to [m, cols] (keeps model-sharded dims where they are)
        red = tuple(range(1, x.ndim))
        out["gram"] = jnp.tensordot(x, x, axes=(red, red))
    return out


def majority_score_ref(G):
    """Paper Algorithm 2, Constraint-2 scores.

    Per column: split workers by the column mean; workers in the larger
    subset score 1 (ties at exactly m/2 favour the >= mean subset, per
    the paper's ``counter < m/2`` negation rule).  Score_i = row sum.
    """
    m = G.shape[0]
    Gf = G.astype(jnp.float32)
    mean_c = jnp.mean(Gf, axis=0, keepdims=True)             # [1,d]
    above = Gf >= mean_c                                     # [m,d]
    n_above = jnp.sum(above, axis=0, keepdims=True)          # [1,d]
    majority_is_above = n_above * 2 >= m                     # counter >= m/2
    M = jnp.where(majority_is_above, above, ~above)
    return jnp.sum(M, axis=1, dtype=jnp.int32).astype(jnp.float32)  # [m]


def l1_to_median_ref(G, med=None):
    if med is None:
        med = cwise_median_ref(G)
    return jnp.sum(jnp.abs(G.astype(jnp.float32) - med[None]), axis=1)


def brsgd_stats_ref(G):
    """One fused pass: (median [d], mean [d], scores [m], l1 [m])."""
    med = cwise_median_ref(G)
    return med, column_mean_ref(G), majority_score_ref(G), l1_to_median_ref(G, med)


def masked_mean_ref(G, mask):
    """Mean of the selected rows (matvec form — Pallas kernel oracle).
    mask: [m] bool/float; float weights give a weighted mean."""
    w = mask.astype(jnp.float32)
    sw = jnp.sum(w)
    return (w @ G.astype(jnp.float32)) / jnp.where(sw > 0, sw, 1.0)


def masked_mean_det(G, mask):
    """Weighted row mean with deterministic sequential accumulation (see
    module docstring): full-mask output is bit-identical to
    ``column_mean_ref``."""
    Gf = G.astype(jnp.float32)
    w = mask.astype(jnp.float32)
    s, _ = jax.lax.scan(lambda c, wr: (c + wr[0] * wr[1], None),
                        jnp.zeros_like(Gf[0]), (w, Gf))
    sw = jnp.sum(w)
    return _exact_div(s, jnp.where(sw > 0, sw, 1.0))


def rank_select(x, k: int):
    """k-th smallest value of the 1-D vector x (0-indexed) WITHOUT
    sorting: counting ranks.  An element is the k-th order statistic iff
    (# strictly smaller) <= k < (# smaller-or-equal); duplicates all
    satisfy the predicate with the same value, so the masked max is
    exact.  Equal to ``jnp.sort(x)[k]`` on finite inputs.

    Replaces the last O(m log m) replicated step of the BrSGD selection
    with O(m)-depth counting (the [m, m] comparison is m <= 64 bools —
    one vector op — while XLA's CPU sort is a scalar loop); the
    per-dimension work that dominates Algorithm 2 stays O(md).
    """
    lt = jnp.sum((x[None, :] < x[:, None]).astype(jnp.int32), axis=1)
    le = jnp.sum((x[None, :] <= x[:, None]).astype(jnp.int32), axis=1)
    hit = (lt <= k) & (k < le)
    return jnp.max(jnp.where(hit, x, -jnp.inf))


def quantile_nearest_index(q: float, m: int) -> int:
    """Index of the ``method='nearest'`` q-quantile of a sorted m-vector,
    with jnp.quantile's tie rule: the virtual index q·(m-1) rounds half
    DOWN (jax selects low_value when the high weight is exactly 0.5;
    numpy's banker's rounding differs at .5 — we pin the jax semantics
    the selection previously compiled to)."""
    virt = q * (m - 1)
    low = math.floor(virt)
    return low if (virt - low) <= 0.5 else low + 1


def brsgd_thresholds(scores, l1, beta: float, threshold):
    """Resolved C1/C2 cutoffs of paper Algorithm 2: (kth score, 𝔗).

    This and ``brsgd_select_mask`` are the ONE copy of the selection
    math — engine.brsgd_select, the fused Pallas wrapper and the jnp
    fused fallback all stage through here (they live below the core
    layer, so the kernels can share them without a circular import).
    Both cutoffs are :func:`rank_select` counting quantiles — no sort
    anywhere in the replicated phase.
    """
    m = scores.shape[0]
    k = max(1, math.ceil(beta * m))
    kth = rank_select(scores, m - k)
    T = jnp.where(threshold > 0, threshold,
                  rank_select(l1, quantile_nearest_index(0.25, m)))
    return kth, T


def brsgd_select_mask(scores, l1, beta: float, threshold):
    """C1∩C2 with the empty-set fallback to C2.
    Returns (selected, c1, c2, 𝔗) — all [m] bool except 𝔗."""
    kth, T = brsgd_thresholds(scores, l1, beta, threshold)
    c1 = l1 <= 2.0 * T
    c2 = scores >= kth
    sel = c1 & c2
    sel = jnp.where(jnp.any(sel), sel, c2)
    return sel, c1, c2, T


def trim_k(trim_frac: float, m: int) -> int:
    """Per-side trim count k = ⌊trim_frac·m⌋, guarded so at least one
    row survives (degenerate trims fall back to median-like k)."""
    k = int(trim_frac * m)
    if 2 * k >= m:
        k = (m - 1) // 2
    return k


# above this worker count the trimmed mean reads enough sorted rows
# that XLA's per-consumer re-fusion of the row-list network costs more
# than the stage-vectorized stack's extra full passes (measured
# crossover between m=32 and m=64 on CPU at d=160k)
_TRIM_STACK_MIN_M = 33


def trimmed_mean_ref(G, trim_frac: float):
    """Coordinate-wise trimmed mean (Yin et al. 2018 baseline): mean of
    the sorted rows k..m-k-1 from the shared sorted-rows pass — the
    row-list network (DCE-pruned) for small m, the stage-vectorized
    stack for larger m (see :func:`sorted_worker_stack`)."""
    m = G.shape[0]
    k = trim_k(trim_frac, m)
    if m >= _TRIM_STACK_MIN_M:
        S = sorted_worker_stack(G)
        return jnp.sum(S[k:m - k], axis=0) / (m - 2 * k)
    rows = sorted_worker_rows(G)
    acc = rows[k]
    for i in range(k + 1, m - k):
        acc = acc + rows[i]
    return acc / (m - 2 * k)


# ---------------------------------------------------------------------------
# elastic (masked) statistics: pad-to-max-m + validity mask
# ---------------------------------------------------------------------------
# Every function below takes ``valid`` ([m] 0/1) naming the ACTIVE
# worker slots of a padded round.  The masking contract is EXACT ZEROS,
# never NaN poison: dropped slots are zeroed with ``jnp.where`` (a
# multiplicative 0 * inf would be NaN), cutoffs and counts are quantiles
# over the active set only, and active counts are traced values — so ONE
# compiled graph serves every active-set size up to max_m.

def quantile_index_dyn(q: float, n):
    """Traced-count twin of :func:`quantile_nearest_index` — same
    virtual index and half-DOWN tie rule, for quorum-sized active sets
    whose count is a runtime value."""
    virt = q * (n.astype(jnp.float32) - 1.0)
    low = jnp.floor(virt)
    return jnp.where(virt - low <= 0.5, low, low + 1.0).astype(jnp.int32)


def masked_sorted_stack(x, valid):
    """:func:`sorted_worker_stack` with the invalid rows forced to +inf
    so they sink past the active ones: rows [0, n_active) of the result
    are the ascending sort of the ACTIVE values."""
    vb = valid.astype(bool).reshape((x.shape[0],) + (1,) * (x.ndim - 1))
    return sorted_worker_stack(jnp.where(vb, x, jnp.inf))


def masked_median_from_stack(S, n_active):
    """Coordinate-wise median over the first ``n_active`` sorted rows
    (dynamic two-middle average; odd counts read the middle row twice,
    and 0.5·(a+a) == a exactly).  +inf rows past the active prefix are
    replaced by exact zeros when n_active == 0 so downstream masked
    consumers never multiply 0 · inf."""
    na = jnp.maximum(n_active, 1)
    lo = jnp.take(S, (na - 1) // 2, axis=0)
    hi = jnp.take(S, na // 2, axis=0)
    med = 0.5 * (lo + hi)
    return jnp.where(jnp.isfinite(med), med, 0.0)


def masked_stat_refs(G, needs, valid, axis: int = 0) -> dict:
    """The [d]-space invariants of the active set — column mean +
    majority mask (``scores``), coordinate-wise median (``l1`` /
    ``d2med``) — plus the zeroed worker view.

    Computed ONCE per leaf and shared by every arrival bucket's partial
    (``engine.stream_leaf_stats``): per-worker stat rows are functions
    of the worker's own row and these fixed references only, which is
    what makes the streaming fold bit-exact with the bulk masked pass
    (disjoint output slots + IEEE ``x + 0.0 == x``)."""
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)          # [m, ...]
    v = valid.astype(jnp.float32)
    vb = v.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
    x = jnp.where(vb > 0, x, 0.0)
    na = jnp.sum(v)
    refs = {"x": x, "v": v, "na": na}
    if "scores" in needs:
        mean_c = _exact_div(det_sum_rows(x), jnp.maximum(na, 1.0))
        n_above, _ = jax.lax.scan(
            lambda c, gv: (c + gv[1] * (gv[0] >= mean_c).astype(jnp.float32),
                           None),
            jnp.zeros(x.shape[1:], jnp.float32), (x, v))
        refs["mean_c"] = mean_c
        refs["majority_is_above"] = n_above * 2.0 >= na
    if "l1" in needs or "d2med" in needs:
        refs["med"] = masked_median_from_stack(
            masked_sorted_stack(x, v), jnp.sum(v.astype(jnp.int32)))
    return refs


def masked_fused_stats_ref(G, needs, valid, axis: int = 0, rows=None,
                           refs=None) -> dict:
    """Masked variant of :func:`fused_stats_ref`: statistics of the
    active workers only, with every dropped slot an EXACT zero.

    ``rows`` ([m] 0/1, optional) restricts the OUTPUT slots: slots
    outside ``rows`` are zero even when valid — this is the
    per-arrival-bucket partial of the streaming accumulator.  ``refs``
    reuses a :func:`masked_stat_refs` result so all buckets of one leaf
    share identical active-set invariants.  Each output slot depends
    only on that worker's row and the shared refs, so partials over any
    partition of the active set fold (bit-exactly, by disjoint slots)
    into the bulk ``rows=None`` pass."""
    if refs is None:
        refs = masked_stat_refs(G, needs, valid, axis=axis)
    x, v = refs["x"], refs["v"]
    r = v if rows is None else v * rows.astype(jnp.float32)
    out = {}
    if "scores" in needs:
        mean_c = refs["mean_c"]
        maj = refs["majority_is_above"]
        out["scores"] = jax.lax.map(
            lambda gr: gr[1] * jnp.sum(
                jnp.where(maj, gr[0] >= mean_c, gr[0] < mean_c),
                dtype=jnp.int32).astype(jnp.float32), (x, r))
    if "l1" in needs or "d2med" in needs:
        med = refs["med"]

        def dists(gr):
            diff = gr[0] - med
            return (gr[1] * jnp.sum(jnp.abs(diff)),
                    gr[1] * jnp.sum(diff * diff))

        l1, d2med = jax.lax.map(dists, (x, r))
        if "l1" in needs:
            out["l1"] = l1
        if "d2med" in needs:
            out["d2med"] = d2med
    if "gram" in needs:
        red = tuple(range(1, x.ndim))
        xr = jnp.where(r.reshape((x.shape[0],) + (1,) * (x.ndim - 1)) > 0,
                       x, 0.0)
        out["gram"] = jnp.tensordot(xr, x, axes=(red, red))
    return out


def masked_cwise_median_ref(G, valid, axis: int = 0):
    """Coordinate-wise median over the active rows."""
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)
    return masked_median_from_stack(masked_sorted_stack(x, valid),
                                    jnp.sum(valid.astype(jnp.int32)))


def masked_trimmed_mean_ref(G, trim_frac: float, valid, axis: int = 0):
    """Coordinate-wise trimmed mean over the active rows: per-side trim
    k = ⌊trim_frac·n_active⌋ with the :func:`trim_k` degeneracy guard,
    both counts traced."""
    x = jnp.moveaxis(G.astype(jnp.float32), axis, 0)
    m = x.shape[0]
    S = masked_sorted_stack(x, valid)
    na = jnp.sum(valid.astype(jnp.int32))
    k = (trim_frac * na.astype(jnp.float32)).astype(jnp.int32)
    k = jnp.where(2 * k >= na, jnp.maximum(na - 1, 0) // 2, k)
    ranks = jnp.arange(m).reshape((m,) + (1,) * (x.ndim - 1))
    kept = jnp.where((ranks >= k) & (ranks < na - k), S, 0.0)
    return _exact_div(det_sum_rows(kept),
                      jnp.maximum(na - 2 * k, 1).astype(jnp.float32))


def masked_brsgd_select(scores, l1, beta: float, threshold, valid):
    """Masked :func:`brsgd_select_mask`: both cutoffs are counting
    quantiles over the ACTIVE workers (k = ⌈β·n_active⌉ clamped ≥ 1;
    auto-𝔗 = lower quartile of the active l1 at the dynamic
    :func:`quantile_index_dyn`), and no mask ever selects a dropped
    worker.  With a full mask this reduces to the static selection (same
    cutoff values, same tie rules)."""
    m = scores.shape[0]
    v = valid.astype(bool)
    na = jnp.maximum(jnp.sum(v.astype(jnp.int32)), 1)
    k = jnp.clip(jnp.ceil(beta * na.astype(jnp.float32)).astype(jnp.int32),
                 1, na)
    # dropped slots take -inf scores / +inf l1, so active order
    # statistics sit in known rank windows of the full m-vector:
    # the k-th-from-top active score is ascending rank m - k
    kth = rank_select(jnp.where(v, scores, -jnp.inf), m - k)
    T = jnp.where(threshold > 0, threshold,
                  rank_select(jnp.where(v, l1, jnp.inf),
                              quantile_index_dyn(0.25, na)))
    c1 = v & (l1 <= 2.0 * T)
    c2 = v & (scores >= kth)
    sel = c1 & c2
    sel = jnp.where(jnp.any(sel), sel, c2)
    return sel, c1, c2, T
