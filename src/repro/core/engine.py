"""Layout-aware robust-aggregation engine.

One registry drives every aggregation rule in every execution layout.
Before this module existed the same per-leaf statistics math was written
three times (jnp reference, Pallas kernel wrapper, and inline shard_map
code) and only 3 of the 7 registered aggregators could run distributed.

Registry contract
-----------------
An :class:`AggregatorSpec` declares WHAT an aggregator needs, never HOW
a layout obtains it.  Exactly one of ``select``/``column`` is set:

* ``stats``  — the per-leaf statistics the rule consumes, a subset of
  :data:`STAT_NAMES`:

    ``scores``  [m]    majority scores (paper Alg. 2 Constraint 2)
    ``l1``      [m]    l1 distance to the coordinate-wise median
    ``d2med``   [m]    squared l2 distance to the coordinate-wise median
    ``gram``    [m,m]  pairwise Gram matrix G Gᵀ (pairwise distances
                       d²_ij = S_ii + S_jj − 2 S_ij derive from it)

  Every statistic is additive over disjoint dimension ranges, so a
  layout may compute it per leaf / per shard and sum (and, for the
  ``a2a`` layout, ``psum``) the partials.

* ``select`` — replicated rule ``(stats, cfg, m) -> (weights [m] f32,
  state | None)``.  Runs on [m]-/[m,m]-sized inputs only, identically on
  every device.  The engine then emits the weighted row combine
  ``Σ_i w_i g_i / Σ_i w_i`` in whatever layout is active.

* ``column`` — per-dimension rule ``(G [m, cols], cfg, m, **kw) ->
  [cols]`` for aggregators that are a pure map over dimensions (e.g.
  coordinate-wise median / trimmed mean).  Needs no replicated phase at
  all: each device applies it to the worker values it holds.

Adding an aggregator is one :func:`register` call — it is then
automatically available in all three layouts, to ``benchmarks/`` and to
``training/step.py``.

Layouts
-------
``local``   single-host worker-gradient matrix G [m, d] (the paper's
            experimental setting; Pallas kernels when on TPU).
``gather``  inside shard_map: all_gather per leaf over the worker axes
            — every device redundantly holds all m workers' values for
            the dims it owns (paper-faithful "master collects G").
            Select rules gather each leaf exactly ONCE, for the fused
            stats pass; the gathered view is transient (peak m× one
            leaf, not m× the model) because the weighted combine is a
            psum of each worker's own weighted gradient, never a second
            pass over gathered data.
``a2a``     inside shard_map: flatten, zero-pad to m·⌈D/m⌉, all_to_all
            — each device owns ALL workers for 1/m of the dims (1×
            transient memory); per-worker stats finish with one psum of
            [m]-vectors, the aggregated chunk is re-assembled with a
            tiled all_gather.  Zero-pad columns contribute +1 per
            worker to ``scores`` (subtracted globally) and 0 to every
            other statistic.

All layouts share :func:`leaf_stats` — the per-leaf statistics math is
written exactly once.
"""
from __future__ import annotations

import logging
import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..compat import axis_size
from ..configs.base import ByzantineConfig
from ..kernels import ops, ref

# canonical stat names live at the kernel layer (ref.py) so the fused
# Pallas/jnp passes can share them without a circular import
STAT_NAMES = ref.STAT_NAMES

GEOMEDIAN_ITERS = 16
GEOMEDIAN_EPS = 1e-6


# ---------------------------------------------------------------------------
# BrSGD selection (paper Algorithm 2) — the replicated phase
# ---------------------------------------------------------------------------

class SelectionState(NamedTuple):
    """Generic diagnostics for select-rule aggregators that have no
    richer state of their own (krum: one row; multi_krum: m-f rows;
    geomedian: all rows, continuously weighted).  ``selected`` feeds
    the training loop's n_selected metric."""
    selected: jax.Array     # [m] bool — rows with nonzero combine weight
    weights: jax.Array      # [m] f32 — the combine weights


class BrSGDState(NamedTuple):
    """Diagnostics of one aggregation call (useful for tests/monitoring)."""
    selected: jax.Array     # [m] bool — C1 ∩ C2 (after fallback)
    c1: jax.Array           # [m] bool — l1 filter
    c2: jax.Array           # [m] bool — top-beta score filter
    scores: jax.Array       # [m]
    l1: jax.Array           # [m]
    threshold: jax.Array    # resolved 𝔗


def brsgd_select(scores, l1, beta: float, threshold: float) -> BrSGDState:
    """Constraint 1 (ℓ1 ≤ 2𝔗) ∩ Constraint 2 (top-β by score).

    threshold <= 0 selects the auto rule 𝔗 = lower-quartile_i(l1_i):
    under honest majority (α < 1/2) the 25th percentile of the l1
    distances is attained by an honest worker, and — unlike the median —
    it stays honest at the paper's boundary setting α = 1/2, where the
    per-dimension majority tie-break alone is adversarially exploitable
    (an attacker cluster of exactly m/2 identical rows wins every tie on
    dimensions whose honest gradient sum has the right sign).  2𝔗 then
    covers the honest concentration radius (Assumption 1) while the
    Byzantine cluster's l1 — inflated by its own distance to the honest
    median — is rejected.
    """
    sel, c1, c2, T = ref.brsgd_select_mask(scores, l1, beta, threshold)
    return BrSGDState(sel, c1, c2, scores, l1, T)


# ---------------------------------------------------------------------------
# per-leaf statistics — written ONCE, used by every layout
# ---------------------------------------------------------------------------

def leaf_stats(G, needs, m: int, axis: int = 0,
               use_pallas: bool | None = None, valid=None, rows=None,
               refs=None) -> dict:
    """Partial statistics of one worker view of G (f32), whose ``axis``
    indexes the m workers (worker-major [m, cols] by default).

    G may be a full local matrix, a gathered leaf, an all_to_all chunk,
    or a blocked-scope worker view with the worker axis in the middle of
    an N-D leaf — the returned partials are additive over the dimension
    ranges the views cover (psum over workers completes the a2a and
    blocked layouts).

    Delegates to ``ops.fused_stats`` — ONE pass over the view, however
    many statistics the spec declared: one HBM read on TPU, one shared
    bitonic sorted-rows pass on the reference path (the seed's version
    re-derived the coordinate-wise median per statistic through XLA's
    scalarized CPU sort).  DESIGN.md §Perf has the contract.

    ``valid`` ([m] 0/1) switches to the elastic masked pass: statistics
    of the active workers only, dropped slots as exact zeros (DESIGN.md
    §Elastic).  ``rows``/``refs`` scope the output to one arrival
    bucket against shared active-set invariants — the streaming-
    accumulator hooks (:func:`stream_leaf_stats`).
    """
    if not needs:
        return {}
    kw = {} if use_pallas is None else {"use_pallas": use_pallas}
    if valid is not None:
        kw.update(valid=valid, rows=rows, refs=refs)
    return ops.fused_stats(G, tuple(sorted(needs)), axis=axis, **kw)


def zero_stats(needs, m: int) -> dict:
    """Zero-initialized partial-stat accumulators for ``needs``."""
    return {k: jnp.zeros((m, m) if k == "gram" else (m,), jnp.float32)
            for k in needs}


def resolve_select(spec, stats: dict, cfg, m: int):
    """Run a spec's replicated select rule and resolve the combine
    denominator: ``(weights [m], state, denom)`` with the empty-selection
    guard (Σw == 0 -> divide by 1) and a synthesized SelectionState when
    the rule has no richer state.  Shared by every layout that emits the
    weighted row combine (sharded gather/a2a and the blocked scope).

    In an elastic round the validity mask rides the stats dict under the
    ``"valid"`` key: every shipped select rule masks its own quantiles
    and candidates, and this resolver re-masks the weights as defense in
    depth — no rule may keep combine weight on a dropped worker."""
    w, st = spec.select(stats, cfg, m)
    valid = stats.get("valid") if isinstance(stats, dict) else None
    if valid is not None:
        w = w * (valid > 0).astype(jnp.float32)
        if st is not None and hasattr(st, "_replace"):
            st = st._replace(selected=st.selected & (valid > 0))
    if st is None:
        st = SelectionState(w > 0, w)
    sw = jnp.sum(w)
    return w, st, jnp.where(sw > 0, sw, 1.0)


def pad_correction(stats: dict, pad, valid=None) -> dict:
    """Remove the zero-pad columns' contribution (a2a layout).

    A zero column means every worker ties at the column mean, so the
    whole column is "majority": +1 score per worker per pad column — per
    ACTIVE worker in an elastic round (dropped slots carry exact-zero
    scores, so their correction is masked too).  Median/l1/d2med/gram of
    zero columns are exactly zero.
    """
    if "scores" in stats and pad:
        stats = dict(stats)
        corr = pad if valid is None else pad * valid.astype(jnp.float32)
        stats["scores"] = stats["scores"] - corr
    return stats


# ---------------------------------------------------------------------------
# streaming (elastic) accumulator — arrival-order-invariant by construction
# ---------------------------------------------------------------------------
# Workers report in arbitrary order; their stat partials fold into a
# running state as they land.  Bit-exactness with the bulk masked
# :func:`leaf_stats` pass is by CONSTRUCTION, not by tolerance: each
# worker's output slots are non-zero in exactly one bucket's partial and
# exact zeros everywhere else (the masked zero-pad contract), the
# [d]-space invariants (column mean / majority / median) are computed
# once from the full active set and shared by every bucket, and IEEE
# ``x + 0.0 == x`` makes dict addition over disjoint slots the identity
# on each slot.  Any permutation or partition of the arrivals therefore
# folds to the same bits.  DESIGN.md §Elastic.

class StreamState(NamedTuple):
    """Running state of the streaming accumulator."""
    stats: dict             # per-worker stat partials folded so far
    valid: jax.Array        # [m] f32 — 1.0 once a worker's partial landed


def init_stream(needs, m: int) -> StreamState:
    return StreamState(zero_stats(needs, m), jnp.zeros((m,), jnp.float32))


def fold_stats(state: StreamState, partial: dict, valid) -> StreamState:
    """Fold one arrival bucket's per-worker stat partials (plus its
    [m] 0/1 arrival mask) into the running state."""
    return StreamState(
        {k: state.stats[k] + partial[k] for k in state.stats},
        state.valid + valid.astype(jnp.float32))


def fold_arrivals(buffer, valid, rows, mask):
    """G-space half of the accumulator: write one arrival bucket's
    gradient rows into the padded [max_m, ...] buffer.  Disjoint slots —
    bit-exact under any arrival order.  Returns (buffer', valid')."""
    mb = mask.astype(jnp.float32).reshape(
        (buffer.shape[0],) + (1,) * (buffer.ndim - 1))
    return jnp.where(mb > 0, rows, buffer), valid + mask.astype(jnp.float32)


def stream_leaf_stats(G, needs, m: int, arrival, axis: int = 0) -> StreamState:
    """Fold per-worker stat partials over a ``lax.scan`` of arrival
    buckets.

    ``arrival`` [n_buckets, m]: disjoint 0/1 masks — bucket b holds the
    workers whose gradients landed in arrival slot b (Σ over buckets is
    the round's validity mask).  The active-set invariants are computed
    ONCE (``ops.masked_stat_refs``); each scan step evaluates the
    bucket's per-worker partials against those fixed references and
    folds them via :func:`fold_stats`.  The returned state's stats are
    bit-exact with ``leaf_stats(G, needs, m, valid=arrival.sum(0))``
    however the workers were bucketed or ordered.
    """
    arrival = arrival.astype(jnp.float32)
    valid = jnp.sum(arrival, axis=0)
    needs_t = tuple(sorted(needs))
    if not needs_t:
        return StreamState({}, valid)
    refs = ops.masked_stat_refs(G, needs_t, valid, axis=axis)

    def body(st, bmask):
        part = leaf_stats(G, needs_t, m, axis=axis, use_pallas=False,
                          valid=valid, rows=bmask, refs=refs)
        return fold_stats(st, part, bmask), None

    state, _ = jax.lax.scan(body, init_stream(needs_t, m), arrival)
    return state


def quorum_met(valid, quorum: int):
    """True once at least ``quorum`` workers' partials have folded in —
    the point selection fires; arrivals past it are dropped."""
    return jnp.sum((valid > 0).astype(jnp.int32)) >= jnp.int32(quorum)


def arrival_active(arrival, quorum: int):
    """[m] f32 quorum mask from [n_buckets, m] arrival buckets: the
    first ``quorum`` workers in arrival order (bucket-major, ties within
    a bucket broken by worker index), dropping everyone later.  0 =
    no quorum (everyone who arrived at all is active)."""
    arrival = arrival.astype(jnp.float32)
    n_buckets, m = arrival.shape
    arrived = jnp.sum(arrival, axis=0) > 0
    if not quorum:
        return arrived.astype(jnp.float32)
    bucket_of = jnp.argmax(arrival, axis=0)            # first (only) bucket
    key = jnp.where(arrived, bucket_of * m + jnp.arange(m),
                    jnp.int32(n_buckets * m + 1) + jnp.arange(m))
    rank = jnp.sum((key[None, :] < key[:, None]).astype(jnp.int32), axis=1)
    return (arrived & (rank < quorum)).astype(jnp.float32)


def stream_aggregate(G, cfg: ByzantineConfig, arrival,
                     spec=None, return_state: bool = False):
    """Local-executor quorum aggregation over a stream of arrival
    buckets: selection fires on the quorum prefix (:func:`arrival_active`
    — at most ``cfg.quorum`` workers), stats fold in bucket by bucket
    (:func:`stream_leaf_stats`), and late arrivals are dropped with
    truthful ``n_selected`` accounting (the returned state's
    ``selected`` never exceeds the quorum)."""
    spec = spec or get_spec(cfg.aggregator)
    m = G.shape[0]
    active = arrival_active(arrival, cfg.quorum)
    if spec.column is not None:
        out = spec.column(G, cfg, m, valid=active, use_pallas=False)
        st = SelectionState(active > 0, active)
        return (out, st) if return_state else out
    state = stream_leaf_stats(G.astype(jnp.float32), spec.stats, m,
                              arrival * active[None, :])
    stats = dict(state.stats)
    stats["valid"] = active
    w, st, _denom = resolve_select(spec, stats, cfg, m)
    Gz = jnp.where(active[:, None] > 0, G.astype(jnp.float32), 0.0)
    agg = ref.masked_mean_det(Gz, w)
    return (agg, st) if return_state else agg


# ---------------------------------------------------------------------------
# aggregator registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AggregatorSpec:
    """Layout-independent description of one aggregation rule."""
    name: str
    stats: frozenset = frozenset()
    select: Optional[Callable] = None   # (stats, cfg, m) -> (w [m], state)
    column: Optional[Callable] = None   # (G [m,cols], cfg, m, **kw) -> [cols]

    def __post_init__(self):
        if (self.select is None) == (self.column is None):
            raise ValueError(
                f"{self.name}: exactly one of select/column must be set")
        unknown = set(self.stats) - set(STAT_NAMES)
        if unknown:
            raise ValueError(f"{self.name}: unknown stats {sorted(unknown)}")


_REGISTRY: dict[str, AggregatorSpec] = {}


def register(spec: AggregatorSpec) -> AggregatorSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_spec(name: str) -> AggregatorSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown aggregator {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def registered() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---- selection rules -------------------------------------------------------
# Every rule handles the elastic case by reading the optional "valid"
# key of the stats dict: byzantine-tolerance counts (krum's f, brsgd's
# top-β) become traced functions of the ACTIVE count, dropped workers'
# rows/columns are pushed to ±inf sentinels so they can never win a
# quantile or a nearest-neighbour window, and returned weights are zero
# on dropped slots (resolve_select re-masks as defense in depth).

def _ones_select(stats, cfg, m):
    valid = stats.get("valid") if isinstance(stats, dict) else None
    if valid is not None:
        return valid.astype(jnp.float32), None
    return jnp.ones((m,), jnp.float32), None


def _brsgd_select_rule(stats, cfg, m):
    valid = stats.get("valid")
    if valid is None:
        st = brsgd_select(stats["scores"], stats["l1"], cfg.beta,
                          cfg.threshold)
    else:
        sel, c1, c2, T = ref.masked_brsgd_select(
            stats["scores"], stats["l1"], cfg.beta, cfg.threshold, valid)
        st = BrSGDState(sel, c1, c2, stats["scores"], stats["l1"], T)
    return st.selected.astype(jnp.float32), st


def _krum_f(cfg, m: int) -> int:
    return cfg.krum_f if cfg.krum_f > 0 else max(1, int(cfg.alpha * m))


def _krum_f_dyn(cfg, na):
    """Traced-count twin of :func:`_krum_f` (same floor/clamp rules)."""
    if cfg.krum_f > 0:
        return jnp.int32(cfg.krum_f)
    return jnp.maximum(1, (cfg.alpha * na.astype(jnp.float32))
                       .astype(jnp.int32))


def _krum_scores(gram, cfg, m: int, valid=None):
    """Krum score_i = Σ of the n-f-2 smallest d²_ij, from the Gram
    matrix (n = m, or the traced active count in an elastic round —
    dropped workers' rows AND columns are +inf, so they neither score
    nor appear in anyone's nearest-neighbour window)."""
    diag = jnp.diagonal(gram)
    d2 = diag[:, None] + diag[None, :] - 2.0 * gram
    d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf))
    if valid is None:
        n_close = max(1, m - _krum_f(cfg, m) - 2)
        return jnp.sum(jnp.sort(d2, axis=1)[:, :n_close], axis=1)
    v = valid > 0
    na = jnp.sum(v.astype(jnp.int32))
    n_close = jnp.maximum(na - _krum_f_dyn(cfg, na) - 2, 1)
    d2 = jnp.where(v[None, :], d2, jnp.inf)
    d2s = jnp.sort(d2, axis=1)
    keep = jnp.arange(m)[None, :] < n_close
    score = jnp.sum(jnp.where(keep, d2s, 0.0), axis=1)
    return jnp.where(v, score, jnp.inf)


def _krum_select(stats, cfg, m):
    score = _krum_scores(stats["gram"], cfg, m, stats.get("valid"))
    return jax.nn.one_hot(jnp.argmin(score), m, dtype=jnp.float32), None


def _multi_krum_select(stats, cfg, m, n_select: int = 0):
    valid = stats.get("valid")
    score = _krum_scores(stats["gram"], cfg, m, valid)
    if valid is None:
        k = min(m, n_select or max(1, m - _krum_f(cfg, m)))
        best = jnp.argsort(score)[:k]
        return jnp.zeros((m,), jnp.float32).at[best].set(1.0), None
    v = valid > 0
    na = jnp.sum(v.astype(jnp.int32))
    k = jnp.clip(jnp.int32(n_select) if n_select
                 else jnp.maximum(na - _krum_f_dyn(cfg, na), 1),
                 1, jnp.maximum(na, 1))
    order = jnp.argsort(score)                 # dropped (inf) rank last
    w = jnp.zeros((m,), jnp.float32).at[order].set(
        (jnp.arange(m) < k).astype(jnp.float32))
    return w * v.astype(jnp.float32), None


def _geomedian_select(stats, cfg, m, iters: int = GEOMEDIAN_ITERS,
                      eps: float = GEOMEDIAN_EPS):
    """Weiszfeld in weight space: z_t is always a row combination
    Σ w_i g_i / Σ w_i, so distances to it derive from the Gram matrix
    (‖g_i − z‖² = S_ii − 2(Sw)_i/W + wᵀSw/W²) — no per-dimension state
    crosses workers after the one-time stats pass.

    Initialized at the coordinate-wise median (via the ``d2med`` stat) —
    starting from the MEAN under a scale-1e10 attack leaves Weiszfeld in
    the flat far-field where all distances (hence weights) are equal.

    Elastic rounds re-mask the weights EVERY iteration: a dropped slot's
    d2med partial is an exact zero, which would otherwise give it the
    1/eps ceiling weight and let garbage dominate the fixed point.
    """
    valid = stats.get("valid")
    vf = None if valid is None else (valid > 0).astype(jnp.float32)
    S = stats["gram"]
    diag = jnp.diagonal(S)
    w = 1.0 / jnp.maximum(jnp.sqrt(stats["d2med"]), eps)
    if vf is not None:
        w = w * vf

    def step(w, _):
        W = jnp.sum(w)
        Sw = S @ w
        d2 = diag - 2.0 * Sw / W + (w @ Sw) / (W * W)
        w2 = 1.0 / jnp.maximum(jnp.sqrt(jnp.maximum(d2, 0.0)), eps)
        return (w2 if vf is None else w2 * vf), None

    w, _ = jax.lax.scan(step, w, None, length=max(iters - 1, 0))
    return w, None


# ---- per-dimension (column) rules ------------------------------------------

def _median_column(G, cfg, m, valid=None, **kw):
    if valid is not None:
        return ops.cwise_median(G, valid=valid, **kw)
    return ops.cwise_median(G, **kw)


def _trimmed_mean_column(G, cfg, m, valid=None, **kw):
    if valid is not None:
        return ops.trimmed_mean(G, trim_frac=cfg.trim_frac, valid=valid, **kw)
    return ops.trimmed_mean(G, trim_frac=cfg.trim_frac, **kw)


# ---- registry entries (the 7 shipped rules) --------------------------------

register(AggregatorSpec("mean", select=_ones_select))
register(AggregatorSpec("median", column=_median_column))
register(AggregatorSpec("trimmed_mean", column=_trimmed_mean_column))
register(AggregatorSpec("krum", stats=frozenset({"gram"}),
                        select=_krum_select))
register(AggregatorSpec("multi_krum", stats=frozenset({"gram"}),
                        select=_multi_krum_select))
register(AggregatorSpec("geomedian", stats=frozenset({"gram", "d2med"}),
                        select=_geomedian_select))
register(AggregatorSpec("brsgd", stats=frozenset({"scores", "l1"}),
                        select=_brsgd_select_rule))


def spec_with(name: str, **select_kwargs) -> AggregatorSpec:
    """Spec variant with extra keyword arguments bound into its select
    rule (e.g. multi_krum n_select, geomedian iters/eps)."""
    spec = get_spec(name)
    return replace(spec, select=partial(spec.select, **select_kwargs))


def expected_collectives(spec: AggregatorSpec, layout: str, n_leaves: int,
                         fast_paths: bool = True, plan=None) -> dict:
    """Expected per-step counts of the TRANSIENT data-moving collectives
    (all_gather / all_to_all) :func:`aggregate_sharded` emits — the
    engine's half of the ``one-gather-per-leaf`` lint contract
    (``analysis/rules.py`` checks traced steps against this, so a
    double-gather regression in either place fails loudly):

      gather  each leaf is gathered exactly ONCE (phase-1 fused stats,
              or the column rule's view); the weighted combine is
              gather-free.  Stat-free selects (mean) gather nothing.
      a2a     one all_to_all (chunk) + one tiled all_gather (unchunk)
              per leaf; the mean fast path (pmean) skips both.
      local   no collectives at all.
      auto    per-leaf sum over the resolved ``plan`` (an explicit
              per-leaf layout sequence / LayoutPlan, or — when omitted
              — :data:`LAST_PLAN` from the traced region).
    """
    if layout == "local":
        return {"all_gather": 0, "all_to_all": 0}
    mean_fast = spec.name == "mean" and fast_paths
    if layout == "auto":
        plan = LAST_PLAN if plan is None else plan
        if plan is None:
            raise ValueError("layout='auto' needs the resolved plan "
                             "(none traced yet)")
        layouts = tuple(getattr(plan, "layouts", plan))
        if getattr(plan, "fast_path", False) or mean_fast:
            layouts = ()
        want = {"all_gather": 0, "all_to_all": 0}
        for ll in layouts:
            per = expected_collectives(spec, ll, 1, fast_paths)
            for k in want:
                want[k] += per[k]
        return want
    if layout == "a2a":
        n = 0 if mean_fast else n_leaves
        return {"all_gather": n, "all_to_all": n}
    if layout == "gather":
        needs_view = spec.column is not None or bool(spec.stats)
        return {"all_gather": n_leaves if needs_view else 0,
                "all_to_all": 0}
    raise ValueError(f"unknown layout {layout!r}")


# ---------------------------------------------------------------------------
# local executor — single-host G [m, d]
# ---------------------------------------------------------------------------

def _combine_rows(G, w, use_pallas: bool, d_blk: int | None):
    """Σ_i w_i g_i / Σ_i w_i.  The jnp path accumulates rows in a fixed
    sequential order (ref.masked_mean_det) so results are reproducible
    and mean-degenerate cases are bit-exact; the Pallas path streams G
    through VMEM once."""
    if use_pallas:
        return ops.masked_mean(G, w, use_pallas=True, d_blk=d_blk)
    return ref.masked_mean_det(G.astype(jnp.float32), w)


def aggregate_local(G, cfg: ByzantineConfig, use_pallas: bool | None = None,
                    return_state: bool = False,
                    spec: AggregatorSpec | None = None,
                    d_blk: int | None = None, valid=None):
    """Run one aggregator on the worker-gradient matrix G [m, d] -> [d].

    ``valid`` ([m] 0/1) runs the elastic masked variant: statistics,
    quantiles and the combine cover the active rows only, dropped rows
    contribute exact zeros (DESIGN.md §Elastic).  Masked calls take the
    jnp reference path — the Pallas fast paths assume a full worker set.
    """
    spec = spec or get_spec(cfg.aggregator)
    m = G.shape[0]
    if valid is not None:
        vf = jnp.asarray(valid).astype(jnp.float32)
        if spec.column is not None:
            out = spec.column(G, cfg, m, valid=vf, use_pallas=False)
            st = SelectionState(vf > 0, vf)
            return (out, st) if return_state else out
        stats = dict(leaf_stats(G.astype(jnp.float32), spec.stats, m,
                                use_pallas=False, valid=vf))
        stats["valid"] = vf
        w, st, _denom = resolve_select(spec, stats, cfg, m)
        Gz = jnp.where(vf[:, None] > 0, G.astype(jnp.float32), 0.0)
        agg = ref.masked_mean_det(Gz, w)
        return (agg, st) if return_state else agg

    kw = {} if use_pallas is None else {"use_pallas": use_pallas}
    if spec.column is not None:
        out = spec.column(G, cfg, m, d_blk=d_blk, **kw)
        return (out, None) if return_state else out

    up = ops.default_use_pallas() if use_pallas is None else use_pallas
    if spec.name == "brsgd" and up:
        # fused fast path: pass 1 emits only the [m] partials (no [d]
        # median/mean HBM writes), pass 2 fuses selection + masked mean
        # — G is streamed from HBM exactly twice.
        scores, l1 = ops.brsgd_partials(G, use_pallas=True, d_blk=d_blk)
        agg, w = ops.brsgd_select_combine(G, scores, l1, cfg.beta,
                                          cfg.threshold, use_pallas=True,
                                          d_blk=d_blk)
        if return_state:
            # the kernel's own selection: no second one on the device
            st = brsgd_select(scores, l1, cfg.beta, cfg.threshold)
            return agg, st._replace(selected=w > 0)
        return agg

    stats = leaf_stats(G.astype(jnp.float32), spec.stats, m, use_pallas=up)
    w, st = spec.select(stats, cfg, m)
    agg = _combine_rows(G, w, up, d_blk)
    if return_state and st is None:
        st = SelectionState(w > 0, w)
    return (agg, st) if return_state else agg


# ---------------------------------------------------------------------------
# sharded executors — inside shard_map over the worker axes
# ---------------------------------------------------------------------------

def gather_leaf(g, axes, m: int):
    """all_gather one leaf to a worker-major [m, *leaf_shape] f32 view.
    Kept N-D: flattening to [m, cols] would merge tensor-sharded auto
    ('model') dims into one axis and force XLA to un-shard them around
    the reshape.  The collective moves the leaf in its own dtype
    (§Perf); statistics upcast locally."""
    G = jax.lax.optimization_barrier(jax.lax.all_gather(g, axes))
    return G.astype(jnp.float32)


def a2a_chunk(g, axes, m: int):
    """Flatten one leaf, zero-pad to m·⌈D/m⌉, all_to_all over the worker
    axes -> ([m, ⌈D/m⌉] f32 chunk where row r is worker r's values for
    this device's dim range, n_pad_columns).  The wire moves the leaf's
    own dtype; stats upcast locally (§Perf).  Shared with the blocked
    scope (core.blocked), which routes replicated and non-divisible
    leaves through here so they stay on the 1×-memory a2a path."""
    flat = g.reshape(-1)
    D = flat.shape[0]
    c = math.ceil(D / m)
    x = jnp.pad(flat, (0, m * c - D)).reshape(m, c)
    Gc = jax.lax.optimization_barrier(
        jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0,
                           tiled=False)).astype(jnp.float32)
    return Gc, m * c - D


def unchunk(vec, g, axes):
    """Re-assemble a per-device [⌈D/m⌉] result into the leaf's shape with
    a tiled all_gather, re-replicating in the gradient's own dtype
    (§Perf)."""
    full = jax.lax.all_gather(vec.astype(g.dtype), axes, tiled=True)
    return full[:g.size].reshape(g.shape)


def _model_split(pspec, model_axes) -> int:
    """Number of model shards a leaf is split into under ``pspec`` (1 =
    replicated over the model axes)."""
    if pspec is None or not model_axes:
        return 1
    n = 1
    for entry in pspec:
        names = entry if isinstance(entry, tuple) else (entry,)
        hit = tuple(a for a in names if a in model_axes)
        if hit:
            n *= axis_size(hit)
    return n


def _model_origin(model_axes):
    """1.0 on the devices whose model-axis indices are all zero, else
    0.0 — the mask that keeps model-replicated partials from being
    counted once per model shard in a cross-model psum."""
    ok = jnp.bool_(True)
    for a in model_axes:
        ok = ok & (jax.lax.axis_index((a,)) == 0)
    return ok.astype(jnp.float32)


# the most recent layout="auto" plan resolved by aggregate_sharded —
# trace-time introspection for tests and the lint driver (the plan is
# also logged through the repro.engine logger)
LAST_PLAN = None

_log = logging.getLogger("repro.engine")


def _resolve_plan(spec, m, leaves, layout, plan, elastic,
                  allow_fast_paths):
    """Per-leaf layout list for one aggregation region.  A fixed layout
    broadcasts; "auto" defers to the analytic cost model
    (analysis.costmodel.plan_layouts) over the LOCAL leaf shards —
    deterministic in the shapes, logged, and recorded in LAST_PLAN."""
    global LAST_PLAN
    if layout != "auto":
        return (layout,) * len(leaves)
    if plan is None:
        from ..analysis import costmodel
        plan = costmodel.plan_layouts(
            spec.name, m, [(int(g.size), g.dtype) for g in leaves],
            fast_paths=allow_fast_paths, elastic=elastic)
    layouts = tuple(getattr(plan, "layouts", plan))
    if len(layouts) != len(leaves):
        raise ValueError(f"layout plan covers {len(layouts)} leaves, "
                         f"tree has {len(leaves)}")
    bad = set(layouts) - {"gather", "a2a"}
    if bad:
        raise ValueError(f"layout plan contains unknown layouts {bad}")
    LAST_PLAN = plan
    _log.info("%s", plan.describe() if hasattr(plan, "describe")
              else f"layout plan: {layouts}")
    return layouts


def _worker_origin(axes):
    """1.0 on the devices whose WORKER-axis indices are all zero —
    the mask that keeps worker-replicated gather-leaf stat partials
    from being counted m times when a mixed layout plan closes the
    stats with a worker-axis psum (the a2a leaves' reduction)."""
    ok = jnp.bool_(True)
    for a in axes:
        ok = ok & (jax.lax.axis_index((a,)) == 0)
    return ok.astype(jnp.float32)


def aggregate_sharded(grads, cfg: ByzantineConfig, axes=("data",),
                      layout: str = "gather",
                      spec: AggregatorSpec | None = None,
                      allow_fast_paths: bool = True,
                      flatten_columns: bool = False,
                      model_axes=(), leaf_specs=None, valid=None,
                      plan=None):
    """Aggregate a gradient pytree across the worker mesh axes.

    Must be called inside a FULL-manual shard_map (every mesh axis
    manual): XLA's partial-manual subgroups only support reduce-type
    collectives, so the all_gather/all_to_all paths here cannot coexist
    with auto axes (DESIGN.md §Mesh).  Returns (aggregated pytree —
    identical on every worker, its model shards intact, state | None).
    Any registered aggregator runs in either layout; see the module
    docstring for the layout semantics.

    ``model_axes``/``leaf_specs``: the mesh's tensor-parallel axes and
    each leaf's PartitionSpec.  Leaves sharded over a model axis are
    this device's shard; their statistic partials cover disjoint dim
    ranges across model shards, while model-replicated leaves' partials
    are identical across shards — the executor masks the latter to the
    model-origin devices and closes both with ONE psum over
    worker+model axes (additivity over dimension ranges, the
    ``leaf_stats`` contract).

    ``flatten_columns``: apply gather-layout column rules to N-D leaves
    through a flattened [m, cols] view so the 2-D Pallas kernels stay
    eligible.  Under full-manual the reshape is purely local, so this
    is always safe; it is an opt-in only to keep the N-D jnp path
    testable.

    ``valid`` ([m] 0/1, replicated) runs the elastic round: dropped
    workers' gradients are zeroed on entry (exact zeros — the masking
    contract), statistics/selection cover the active set only, and in
    the a2a layout the validity mask itself RIDES the stats psum as a
    one-hot slot per active worker — the trace-level signal the
    ``masked-psum-validity`` lint rule checks for (DESIGN.md §Elastic).

    ``layout="auto"`` scores gather vs a2a PER LEAF at trace time
    (analysis.costmodel.plan_layouts — big leaves → a2a, tiny leaves →
    gather, stat-free mean → the replicated fast path) and runs the
    mixed plan: one stats psum closes a2a partials over the worker
    axes with gather-leaf partials masked to the worker origin, then
    each leaf combines through its own layout.  ``plan`` overrides the
    model with an explicit per-leaf layout sequence (or LayoutPlan).
    The resolved plan is logged and stored in :data:`LAST_PLAN`.
    """
    if layout not in ("gather", "a2a", "auto"):
        raise ValueError(f"unknown layout {layout!r}")
    spec = spec or get_spec(cfg.aggregator)
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    model_axes = tuple(model_axes)
    m = axis_size(axes)
    leaves, tdef = jax.tree.flatten(grads)
    leaf_layouts = _resolve_plan(spec, m, leaves, layout, plan,
                                 valid is not None, allow_fast_paths)
    any_a2a = "a2a" in leaf_layouts
    if leaf_specs is None:
        spec_leaves = [None] * len(leaves)
    else:
        from jax.sharding import PartitionSpec as P
        # None is a conventional "replicated" spec: keep it as a LEAF
        # (jax.tree would otherwise drop it as an empty subtree and
        # silently misalign every following spec with its gradient)
        spec_leaves = jax.tree.leaves(
            leaf_specs, is_leaf=lambda x: x is None or isinstance(x, P))
        assert len(spec_leaves) == len(leaves), \
            (len(spec_leaves), len(leaves))
    origin = _model_origin(model_axes) if model_axes else None
    elastic = valid is not None
    if elastic:
        vf = jnp.asarray(valid).astype(jnp.float32)
        act_i = vf[jax.lax.axis_index(axes)]
        leaves = [jnp.where(act_i > 0, g, jnp.zeros_like(g))
                  for g in leaves]

    if spec.name == "mean" and allow_fast_paths and not elastic:
        # uniform weights == plain pmean: skip the gather/a2a machinery
        return jax.tree.unflatten(
            tdef, [jax.lax.pmean(g, axes) for g in leaves]), None

    # -- per-dimension rules: no replicated phase at all ----------------
    if spec.column is not None:
        colkw = {"valid": vf, "use_pallas": False} if elastic else {}
        out = []
        for g, ll in zip(leaves, leaf_layouts):
            if ll == "a2a":
                Gc, _pad = a2a_chunk(g, axes, m)
                out.append(unchunk(spec.column(Gc, cfg, m, **colkw),
                                   g, axes))
                continue
            Gv = gather_leaf(g, axes, m)
            if Gv.ndim > 2 and flatten_columns:
                # 2-D view keeps the Pallas column kernels eligible
                # (purely local under full-manual)
                col = spec.column(Gv.reshape(m, -1), cfg, m, **colkw)
            elif Gv.ndim > 2:
                # N-D jnp path (see the blocked-scope column path)
                col = spec.column(Gv, cfg, m, use_pallas=False,
                                  **({"valid": vf} if elastic else {}))
            else:
                col = spec.column(Gv, cfg, m, **colkw)
            out.append(col.astype(g.dtype).reshape(g.shape))
        st = (SelectionState(vf > 0, vf) if elastic else None)
        return jax.tree.unflatten(tdef, out), st

    # -- phase 1: per-leaf stats partials -------------------------------
    # gather layout: each leaf is gathered EXACTLY once, consumed by the
    # fused stats pass, and dropped — nothing m×-sized survives into
    # phase 2, so steady-state transient memory is one gathered leaf
    # instead of the seed's all-leaves cache.  a2a chunks are kept: they
    # are this device's 1/m dim range (1× total), and phase 2 combines
    # them in place.
    stats = zero_stats(spec.stats, m)
    cached, total_pad = [], 0
    # mixed plans: gather-leaf partials are computed from the full
    # gathered view, hence REPLICATED across workers — when a2a leaves
    # force a worker-axis psum they must be masked to the worker origin
    worigin = _worker_origin(axes) if any_a2a else None
    for g, ps, ll in zip(leaves, spec_leaves, leaf_layouts):
        n_split = _model_split(ps, model_axes)
        if ll == "a2a":
            Gv, pad = a2a_chunk(g, axes, m)
            # each model shard pads its own flattened chunk; the psum
            # below sums them, so sharded leaves contribute n_split pads
            total_pad += pad * n_split if n_split > 1 else pad
            cached.append(Gv)
        elif not stats:
            cached.append(None)
            continue        # stat-free select (mean): nothing to gather
        else:
            Gv = gather_leaf(g, axes, m)
            cached.append(None)
        part = leaf_stats(Gv, spec.stats, m,
                          valid=vf if elastic else None)
        if origin is not None and n_split == 1:
            # model-replicated leaf: every model shard would add the
            # same partial — keep only the model-origin copy
            part = {k: v * origin for k, v in part.items()}
        if worigin is not None and ll == "gather":
            part = {k: v * worigin for k, v in part.items()}
        stats = {k: stats[k] + part[k] for k in stats}
    if stats and (any_a2a or model_axes):
        # a2a partials close over the worker axes; model-sharded leaves'
        # partials close over the model axes in the same reduction
        psum_axes = (axes if any_a2a else ()) + model_axes
        if elastic and any_a2a:
            # the validity mask rides the stats psum: each worker
            # contributes its own one-hot slot (masked to the model
            # origin so model shards don't double-count it).  This is
            # the operand the masked-psum-validity lint rule requires —
            # a stats psum without it means some path folded dropped
            # workers' garbage into the selection.
            vpart = jax.nn.one_hot(jax.lax.axis_index(axes), m,
                                   dtype=jnp.float32) * act_i
            stats["valid"] = vpart if origin is None else vpart * origin
        stats = jax.lax.psum(stats, psum_axes)
        stats = pad_correction(stats, total_pad,
                               valid=vf if elastic else None)
    if elastic:
        stats = dict(stats)
        stats.setdefault("valid", vf)

    # -- phase 2: replicated selection + weighted combine ---------------
    w, st, denom = resolve_select(spec, stats, cfg, m)
    out, a2a_idx = [], []
    # gather-free combine: Σᵢ wᵢgᵢ is a psum of each worker's OWN
    # weighted gradient — no leaf is gathered twice and no gathered
    # copy crosses the phase boundary.  The psum runs in f32 (a
    # weighted reduction; 2L wire vs the (m-1)L a re-gather costs).
    wi = (w[jax.lax.axis_index(axes)] if "gather" in leaf_layouts
          else None)
    for i, (g, Gv, ll) in enumerate(zip(leaves, cached, leaf_layouts)):
        if ll == "a2a":
            out.append(unchunk(jnp.tensordot(w, Gv, axes=1) / denom,
                               g, axes))
            a2a_idx.append(i)
        else:
            agg = jax.lax.psum(wi * g.astype(jnp.float32), axes) / denom
            out.append(agg.astype(g.dtype))
    if a2a_idx:
        # stop XLA hoisting the optimizer's f32 upcast back across the
        # all_gather (it would re-widen the wire to f32)
        barred = jax.lax.optimization_barrier(
            tuple(out[i] for i in a2a_idx))
        for i, v in zip(a2a_idx, barred):
            out[i] = v
    return jax.tree.unflatten(tdef, out), st
