"""Blocked (streaming) robust aggregation: every rule registered in
``core.engine`` runs inside the backward scan, with FSDP parameter
gathering fused into the same barrier.

For >20B models the full per-worker gradient matrix G (m × params)
cannot exist on any device set (deepseek-v2: m=32 × 472 GB).  Every
statistic in the engine registry is additive over disjoint dimension
ranges, so we run any registered aggregator per *bucket* (one
transformer layer-stack slice, or the top-level embed/head bucket) with
bucket-local selections — aggregation happens the moment a layer's
gradients are produced by the backward scan, and only one layer's worth
of cross-worker state is ever live.

Mesh contract (DESIGN.md §Mesh): the barrier runs inside a FULL-manual
shard_map whose manual axes are EVERY mesh axis, and the worker axes
are every mesh axis too — a tensor-parallel 'model' axis is folded
into the FSDP worker set by the step builder (XLA's partial-manual
subgroups cannot lower the all_to_all/all_gather/axis_index this
barrier needs, and per-layer TP would be re-gathered here anyway).

The mechanism is a ``jax.custom_vjp`` barrier applied to each scanned
layer slice (see ``transformer.forward(param_hook=...)``):

  forward :  p_full = all_gather(p_shard) over the worker axes
             (FSDP streaming — params live sharded over workers)
  backward:  g_full (this worker's layer gradient)
             -> optional Byzantine attack injection (``threat.inject``
                — any registered AttackSpec, incl. alie/ipm whose
                honest-statistics psum per bucket; noise key per bucket
                via :func:`bucket_key`, membership from the raw step
                key so all buckets corrupt one worker set)
             -> worker×dims all_to_all re-shard: FSDP leaves transpose
                in place along their own sharded dim; replicated and
                non-divisible (d % m != 0) leaves flatten through
                ``engine.a2a_chunk`` with zero-padding, so EVERY leaf
                stays on the 1×-memory a2a path (no all_gather
                fallback; ``engine.pad_correction`` removes the pad
                columns' score contribution)
             -> ``engine.leaf_stats`` partials (ONE fused pass per
                view — every statistic the rule declares from a single
                read, DESIGN.md §Perf), one psum, the registry
                ``select`` or ``column`` rule, weighted combine
             -> returns the aggregated gradient's local FSDP shard,
                plus the bucket's n_selected histogram on the selection
                token's cotangent

so the optimizer consumes already-aggregated, already-sharded grads and
the training loop reads truthful per-bucket selection counts.
Deviation from the paper (documented in DESIGN.md §2): selections are
per-bucket instead of global.  tests/test_blocked.py asserts
blocked-vs-global parity for every registered aggregator (single
bucket == global selection) and that the selection stays truthful
under attack.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import axis_size
from ..configs.base import ByzantineConfig
from ..models.params import shard_hint
from . import engine, threat


def _fsdp_dim(spec: P, axes) -> int | None:
    """Index of the dim sharded over the worker axes in ``spec``."""
    want = tuple(axes) if len(axes) > 1 else axes[0]
    for i, e in enumerate(spec):
        if e == want or (isinstance(e, tuple) and set(e) == set(axes)):
            return i
    return None


def _gather_leaf(x, dim: int | None, axes):
    if dim is None:
        return x
    return jax.lax.all_gather(x, axes, axis=dim, tiled=True)


def _a2a_worker_view(g, dim: int, m: int):
    """[..., d, ...] -> [..., m, d/m, ...] with dim ``dim`` (size m)
    indexing workers after the all_to_all."""
    s = g.shape
    g = g.reshape(s[:dim] + (m, s[dim] // m) + s[dim + 1:])
    return g


def _shard_view(g, spec: P, k: int, m: int, axes):
    """In-place a2a worker view of one FSDP leaf: [..., d_k, ...] ->
    f32 [..., m, d_k/m, ...] with the worker axis at ``k`` (no flatten,
    no pad — the leaf's own sharded dim is split instead)."""
    # §Perf: collectives move the gradient in ITS OWN dtype (bf16 for
    # bf16 params — half the wire bytes); statistics upcast locally
    # AFTER the optimization barrier, which stops XLA hoisting the f32
    # convert to BEFORE the collective (that would double wire bytes).
    x = _a2a_worker_view(g, k, m)
    # under the full-manual step every mesh axis is a worker axis, so
    # spec entries can only reference ``axes`` and the hint below is a
    # no-op; it is kept for spec-generality (a non-worker entry would
    # need its sharding preserved through the re-shard)
    vspec = []
    for i, e in enumerate(spec):
        ent = None if (e == tuple(axes) or e in axes
                       or (isinstance(e, tuple)
                           and set(e) & set(axes))) else e
        vspec.extend([None, None] if i == k else [ent])
    x = shard_hint(x, P(*vspec))
    Gw = jax.lax.all_to_all(x, axes, split_axis=k, concat_axis=k,
                            tiled=False)
    Gw = jax.lax.optimization_barrier(Gw)
    Gw = shard_hint(Gw, P(*vspec))
    return Gw.astype(jnp.float32)


def _bucket_aggregate(g_full, specs, bcfg: ByzantineConfig, axes,
                      valid=None):
    """Aggregate one bucket of per-worker gradients via the engine
    registry — any registered rule, not just brsgd/mean.

    g_full: pytree of this worker's gradients (full dims).
    Returns ``(aggregated pytree, SelectionState)``: leaves with an
    FSDP dim come back as the local shard, the rest replicated; the
    state carries the bucket-local selection so the training loop's
    n_selected metric is truthful.

    ``valid`` ([m] 0/1, replicated) runs the bucket elastically:
    dropped workers' gradients are zeroed on entry (exact zeros),
    statistics and the selection cover the active set, and the validity
    mask rides the bucket's stats psum as a one-hot slot — the
    ``masked-psum-validity`` lint contract (DESIGN.md §Elastic).
    """
    m = axis_size(axes)
    spec = engine.get_spec(bcfg.aggregator)
    leaves, tdef = jax.tree.flatten(g_full)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    elastic = valid is not None
    if elastic:
        vf = jnp.asarray(valid).astype(jnp.float32)
        act_i = vf[jax.lax.axis_index(axes)]
        leaves = [jnp.where(act_i > 0, g, jnp.zeros_like(g))
                  for g in leaves]

    # -- phase 0: per-leaf worker views, all on the 1×-memory a2a path.
    # ("shard", Gw, k): FSDP leaf transposed in place, worker axis k.
    # ("flat", Gc, 0):  replicated / non-divisible leaf flattened and
    #                   zero-padded through engine.a2a_chunk.
    views, total_pad = [], 0
    for g, pspec in zip(leaves, spec_leaves):
        k = _fsdp_dim(pspec, axes)
        if k is not None and g.shape[k] % m == 0 and g.shape[k] >= m:
            views.append(("shard", _shard_view(g, pspec, k, m, axes), k))
        else:
            Gc, pad = engine.a2a_chunk(g, axes, m)
            total_pad += pad
            views.append(("flat", Gc, 0))

    # -- per-dimension rules: no stats / replicated phase at all --------
    if spec.column is not None:
        colkw = {"valid": vf, "use_pallas": False} if elastic else {}
        out = []
        for (kind, Gv, k), g in zip(views, leaves):
            if kind == "shard":
                # apply the rule along the worker axis WITHOUT collapsing
                # the remaining (possibly model-sharded) dims — a
                # reshape(m, -1) would force XLA to un-shard the auto
                # axes.  The jnp reference rules are N-D over axis 0;
                # the Pallas kernels are 2-D only, so N-D views pin
                # use_pallas=False (plain XLA, still compiled).
                Gm = jnp.moveaxis(Gv, k, 0)
                kw = dict(colkw) if elastic else (
                    {"use_pallas": False} if Gm.ndim > 2 else {})
                out.append(spec.column(Gm, bcfg, m, **kw).astype(g.dtype))
            else:
                out.append(engine.unchunk(spec.column(Gv, bcfg, m, **colkw),
                                          g, axes))
        st = engine.SelectionState(
            (vf > 0) if elastic else jnp.ones((m,), bool),
            vf if elastic else jnp.ones((m,), jnp.float32))
        return jax.tree.unflatten(tdef, out), st

    # -- phase 1: per-leaf stats partials, one psum ---------------------
    stats = engine.zero_stats(spec.stats, m)
    if stats:
        for kind, Gv, k in views:
            part = engine.leaf_stats(Gv, spec.stats, m, axis=k,
                                     valid=vf if elastic else None)
            stats = {s: stats[s] + part[s] for s in stats}
        if elastic:
            # the validity mask rides the bucket's stats psum (one-hot
            # slot per active worker) — the masked-psum-validity lint
            # rule's required operand
            stats["valid"] = jax.nn.one_hot(
                jax.lax.axis_index(axes), m, dtype=jnp.float32) * act_i
        stats = jax.lax.psum(stats, axes)
        stats = engine.pad_correction(stats, total_pad,
                                      valid=vf if elastic else None)
    if elastic:
        stats = dict(stats)
        stats.setdefault("valid", vf)

    # -- phase 2: replicated selection + weighted combine ---------------
    w, st, denom = engine.resolve_select(spec, stats, bcfg, m)
    out = []
    for (kind, Gv, k), g in zip(views, leaves):
        if kind == "shard":
            agg = jnp.tensordot(w, Gv, axes=([0], [k])) / denom
            out.append(agg.astype(g.dtype))
        else:
            out.append(engine.unchunk(jnp.tensordot(w, Gv, axes=1) / denom,
                                      g, axes))
    return jax.tree.unflatten(tdef, out), st


def bucket_key(key, name: str):
    """Stable per-bucket attack NOISE key: fold the bucket's name
    (crc32, so the id survives bucket-set reordering) into the step
    key.  Without this every bucket's injected Byzantine noise is
    bit-identical — a correlated attack strictly weaker than the threat
    model (tests/test_blocked.py regression).  The barrier folds this
    INSIDE its backward (the name is static there), so the raw step key
    stays available for the step-wide membership draw — under the
    ``resample`` policy all buckets must corrupt the SAME workers."""
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def selection_token(m: int):
    """Zero token fed to the aggregation barrier alongside the params.

    Its cotangent is the one-hot histogram of the bucket's n_selected
    (length m+1, index = count), so per-bucket selection counts ride
    out of the backward scan on ordinary gradient accumulation: a
    scanned segment's token gradient is the histogram summed over its
    layers."""
    return jnp.zeros((m + 1,), jnp.float32)


def key_carrier(key):
    """PRNG key bit-cast to f32 so it can ride through the aggregation
    barrier as a differentiable-shaped primal input (cotangent: plain
    zeros).  The key CANNOT be closed over by the barrier instead: its
    bwd runs at scan-transposition time, where a closed-over tracer
    (the step key is a shard_map argument) becomes an unlowerable jaxpr
    constant.  Typed (extended-dtype) keys are unwrapped to their
    uint32 data first — the dry-run drives the step with
    ``jax.random.key`` structs."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return jax.lax.bitcast_convert_type(key, jnp.float32)


def barrier_bwd_fn(specs, bcfg: ByzantineConfig, axes, name: str = "lint",
                   elastic: bool = False):
    """Traceable stand-in for ONE barrier round trip: ``run(p_bucket,
    key, active=None) -> (agg bucket, selection histogram)``.

    The returned callable drives :func:`make_fsdp_agg_barrier` through
    ``jax.grad``, so tracing it (inside a shard_map over ``axes``)
    yields a jaxpr containing exactly the barrier's forward gathers AND
    its backward path (attack injection + bucket aggregation) — what
    ``analysis.jaxpr.extract`` and the barrier pin test
    (tests/test_blocked.py) walk for the ``no-worker-gather-in-
    blocked-bwd`` rule, without hand-rolling a vjp at every call site.
    ``p_bucket`` leaves are this device's LOCAL shards (matching
    ``specs``).  ``elastic`` builds the 5-primal elastic barrier;
    ``active`` then defaults to the all-ones mask."""
    axes = tuple(axes)
    barrier = make_fsdp_agg_barrier(specs, bcfg, axes, name,
                                    elastic=elastic)

    def run(p, key, active=None):
        m = axis_size(axes)
        keyf = key_carrier(key)

        def loss(p, tok):
            if elastic:
                act = (jnp.ones((m,), jnp.float32) if active is None
                       else jnp.asarray(active, jnp.float32))
                out = barrier(p, tok, jnp.float32(0), keyf, act)
            else:
                out = barrier(p, tok, jnp.float32(0), keyf)
            return sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree.leaves(out))

        agg, hist = jax.grad(loss, argnums=(0, 1))(p, selection_token(m))
        return agg, hist

    return run


def make_fsdp_agg_barrier(specs, bcfg: ByzantineConfig, axes, name: str,
                          elastic: bool = False):
    """Returns hook(p_bucket, tok, layer_idx, keyf) -> gathered bucket
    with aggregating VJP.

    ``specs``: PartitionSpec pytree matching the bucket (one scanned
    layer slice, or the top-level bucket).  ``tok`` is a
    :func:`selection_token`; its cotangent reports the bucket's real
    n_selected as a histogram (see training/step.py).  ``layer_idx``
    (f32 scalar — f32 so its cotangent is a plain zero) is the position
    inside the bucket's scan, folded into the attack noise key so the
    layers of ONE scanned segment receive different noise too — the
    per-bucket :func:`bucket_key` (folded here from the static
    ``name``) alone would repeat noise across a segment's layers, which
    all share this one hook.  ``keyf`` is the RAW step key via
    :func:`key_carrier`; the bucket/layer folds perturb only the noise,
    while byzantine MEMBERSHIP is drawn from the unfolded step key so
    every bucket corrupts one consistent worker set
    (``threat.membership_mask``).

    ``elastic`` adds a fifth primal ``activef`` ([m] f32 validity mask,
    replicated; cotangent plain zeros like ``keyf``): the bucket's
    injection and aggregation then run over the active set only.  The
    mask is a TRACED value, so one compiled step serves every active
    set up to m — the flag is static (two barrier variants) but the
    mask is not."""
    axes = tuple(axes)

    if elastic:
        @jax.custom_vjp
        def barrier(p, tok, idx, keyf, activef):
            del tok, idx, keyf, activef
            return jax.tree.map(
                lambda x, s: _gather_leaf(x, _fsdp_dim(s, axes), axes),
                p, specs)

        def fwd(p, tok, idx, keyf, activef):
            return barrier(p, tok, idx, keyf, activef), (idx, keyf, activef)

        def bwd(res, g_full):
            idx, keyf, activef = res
            key = jax.lax.bitcast_convert_type(keyf, jnp.uint32)
            key_l = jax.random.fold_in(bucket_key(key, name),
                                       idx.astype(jnp.int32))
            with jax.named_scope("aggregate"):
                g_full = threat.inject(g_full, key_l, bcfg, axes,
                                       membership_key=key, active=activef)
                agg, st = _bucket_aggregate(g_full, specs, bcfg, axes,
                                            valid=activef)
                m = axis_size(axes)
                n_sel = jnp.sum(st.selected.astype(jnp.int32))
                hist = jax.nn.one_hot(n_sel, m + 1, dtype=jnp.float32)
            return (agg, hist, jnp.zeros((), jnp.float32),
                    jnp.zeros_like(keyf), jnp.zeros_like(activef))

        barrier.defvjp(fwd, bwd)
        return barrier

    @jax.custom_vjp
    def barrier(p, tok, idx, keyf):
        del tok, idx, keyf
        return jax.tree.map(
            lambda x, s: _gather_leaf(x, _fsdp_dim(s, axes), axes), p, specs)

    def fwd(p, tok, idx, keyf):
        return barrier(p, tok, idx, keyf), (idx, keyf)

    def bwd(res, g_full):
        idx, keyf = res
        key = jax.lax.bitcast_convert_type(keyf, jnp.uint32)
        key_l = jax.random.fold_in(bucket_key(key, name),
                                   idx.astype(jnp.int32))
        with jax.named_scope("aggregate"):
            g_full = threat.inject(g_full, key_l, bcfg, axes,
                                   membership_key=key)
            agg, st = _bucket_aggregate(g_full, specs, bcfg, axes)
            m = axis_size(axes)
            n_sel = jnp.sum(st.selected.astype(jnp.int32))
            hist = jax.nn.one_hot(n_sel, m + 1, dtype=jnp.float32)
        return agg, hist, jnp.zeros((), jnp.float32), jnp.zeros_like(keyf)

    barrier.defvjp(fwd, bwd)
    return barrier
