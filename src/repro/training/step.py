"""Distributed train-step builder.

Mesh execution strategy (DESIGN.md §Mesh): XLA's partial-manual
subgroups only support reduce-type collectives — worker all_gather /
all_to_all / axis_index (and any lax.scan) must live in a FULL-manual
region with no auto axis — so every region's manual axes are explicit
per scope:

  global scope  : auto-SPMD loss + ONE full-manual aggregation region.
                  The loss is a vmap over the batch's worker axis under
                  plain jit (NO shard_map): GSPMD shards the vmapped
                  compute over the worker axes and the tensor-parallel
                  math over 'model', like the serving paths.  The
                  per-worker gradient stack then enters a shard_map
                  that is manual over EVERY mesh axis — attack
                  injection + robust aggregation run there, with
                  model-sharded leaves as local shards
                  (engine.aggregate_sharded model_axes/leaf_specs).
                  The optimizer update runs outside in plain auto-SPMD
                  (elementwise math).
  blocked scope : ONE full-manual shard_map over EVERY mesh axis, with
                  all axes acting as FSDP worker axes (a 'model' axis
                  is folded into the worker set — launch.mesh
                  worker_axes(scope="blocked")).  FSDP params +
                  aggregation inside the backward scan (core.blocked)
                  — the >20B path.  Any registered aggregator runs
                  per-bucket; each bucket's real n_selected rides out
                  of the backward on a selection token's cotangent (a
                  histogram over counts), so the n_selected /
                  n_selected_min metrics are truthful — the seed
                  hard-coded n_selected == m here.

The builder returns the jitted step plus the sharding trees needed by
both the real driver and the dry-run (which feeds ShapeDtypeStructs).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import shard_map
from ..configs.base import ByzantineConfig, ModelConfig, TrainConfig
from ..core import threat
from ..core.blocked import key_carrier, make_fsdp_agg_barrier, selection_token
from ..core.distributed import robust_aggregate
from ..launch.mesh import n_workers, worker_axes
from ..models import params as PM
from ..models import transformer as TF
from ..optim import get_optimizer

GIANT_PARAMS = 20e9
# an MoE model's routing counters in the step's metrics: the (token,
# expert) pairs its held experts computed, summed over layers and
# workers, and the fullest held expert's load over the held mean, the
# largest over layers and workers
MOE_COUNTERS = ("moe_pairs", "moe_load_max")


def resolve_strategy(tcfg: TrainConfig) -> tuple[str, str]:
    """(scope, layout) with 'auto' resolved by model size.

    Global-scope ``agg_layout="auto"`` stays "auto": the engine scores
    gather vs a2a PER LEAF at trace time through the analytic cost
    model (analysis.costmodel.plan_layouts — big leaves → a2a, tiny
    leaves → gather, stat-free mean → the replicated fast path) and
    logs the resolved plan.  The blocked scope runs its per-bucket a2a
    barrier regardless; explicit "gather"/"a2a" force a uniform layout
    (the paper baseline / EXPERIMENTS.md §Perf pair 2 setting)."""
    n = PM.count_params(TF.param_defs(tcfg.model))
    scope = tcfg.agg_scope
    if scope == "auto":
        scope = "blocked" if n > GIANT_PARAMS else "global"
    layout = tcfg.agg_layout
    if layout == "auto" and scope == "blocked":
        layout = "a2a"
    return scope, layout


class StepBundle(NamedTuple):
    step_fn: object             # jitted (params, opt, batch, step, key) -> ...
    param_specs: object         # PartitionSpec pytree
    opt_specs: object
    batch_specs: dict
    scope: str
    layout: str

    def shardings(self, mesh):
        to_sh = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        return to_sh(self.param_specs), to_sh(self.opt_specs), to_sh(self.batch_specs)


def _opt_state_specs(opt_name: str, pspecs):
    if opt_name == "sgd":
        return ()
    if opt_name == "momentum":
        return pspecs
    if opt_name == "adamw":
        return {"m": pspecs, "v": pspecs}
    raise ValueError(opt_name)


def _layer_slice_specs(specs):
    """Drop the leading stack-dim entry of every leaf spec (the scan
    consumes it)."""
    return jax.tree.map(lambda s: P(*s[1:]) if len(s) else s, specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs_for(cfg: ModelConfig, waxes) -> dict:
    w = tuple(waxes) if len(waxes) > 1 else waxes[0]
    out = {"tokens": P(w)}
    if cfg.n_prefix_tokens:
        out["prefix_embed"] = P(w)
    return out


def _local_batch(batch):
    """Squeeze the (locally size-1) sharded worker axis."""
    return {k: v.reshape(v.shape[1:]) if v.shape[0] == 1 else v[0]
            for k, v in batch.items()}


def _build_blocked_step(tcfg, mesh, opt, layout):
    """One FULL-manual shard_map over every mesh axis: FSDP params,
    per-bucket aggregation inside the backward scan."""
    cfg = tcfg.model
    bcfg = tcfg.byzantine
    waxes = worker_axes(mesh, "blocked")            # every axis
    m = n_workers(mesh, "blocked")
    defs = TF.param_defs(cfg)
    # tp=False: the 'model' axis acts as extra FSDP workers here, never
    # as tensor parallelism — the whole step is manual over it
    pspecs = PM.pspec_tree(defs, mesh, fsdp=True, tp=False)
    ospecs = _opt_state_specs(tcfg.optimizer, pspecs)
    bspecs = batch_specs_for(cfg, waxes)
    remat = tcfg.remat == "block"
    metric_spec = P()
    elastic = bcfg.elastic
    guard = tcfg.recovery.guard
    # the per-step active mask is a TRACED [m] f32 arg (replicated):
    # one compiled step serves every active set up to m slots —
    # changing who straggles never recompiles (DESIGN.md §Elastic).
    # The guard (§Faults) adds a second traced [m] vector — the grad
    # fault mask — and a per-worker finiteness metric; both replicated,
    # so fault churn never recompiles either.
    extra = (P(), P()) if guard else ((P(),) if elastic else ())
    mspecs = {"loss": metric_spec, "ce": metric_spec,
              "gnorm": metric_spec, "n_selected": metric_spec,
              "n_selected_min": metric_spec}
    if guard:
        mspecs["worker_ok"] = metric_spec
    if cfg.is_moe:
        mspecs.update(dict.fromkeys(MOE_COUNTERS, metric_spec))

    @partial(shard_map, mesh=mesh,
             in_specs=(pspecs, ospecs, bspecs, P(), P(), *extra),
             out_specs=(pspecs, ospecs, mspecs),
             axis_names=set(waxes), check_vma=False)
    def step(params, opt_state, batch, step_idx, key, *rest):
        activef = rest[0] if elastic else None
        faultf = rest[1] if guard else None
        lbatch = _local_batch(batch)
        lspecs = {k: _layer_slice_specs(v) for k, v in pspecs.items()
                  if k.startswith("seg_")}
        top_specs = {k: v for k, v in pspecs.items()
                     if not k.startswith("seg_")}
        # every barrier receives the RAW step key (key_carrier);
        # the bucket name (static, folded inside the barrier bwd)
        # and the scan index decorrelate the injected noise across
        # buckets and layers, while byzantine membership is drawn
        # from the unfolded key so all buckets corrupt ONE worker
        # set (threat.membership_mask, incl. the resample policy)
        barriers = {k: make_fsdp_agg_barrier(v, bcfg, waxes, k,
                                             elastic=elastic)
                    for k, v in lspecs.items()}
        top_barrier = make_fsdp_agg_barrier(top_specs, bcfg, waxes, "top",
                                            elastic=elastic)
        keyf = key_carrier(key)
        toks = {k: selection_token(m) for k in (*barriers, "top")}

        def lfn(params, toks):
            if elastic:
                hooks = {k: (lambda p, i, b=b, t=toks[k]:
                             b(p, t, i, keyf, activef))
                         for k, b in barriers.items()}
                top_hook = lambda p: top_barrier(
                    p, toks["top"], jnp.float32(0), keyf, activef)
            else:
                hooks = {k: (lambda p, i, b=b, t=toks[k]: b(p, t, i, keyf))
                         for k, b in barriers.items()}
                top_hook = lambda p: top_barrier(
                    p, toks["top"], jnp.float32(0), keyf)
            with jax.named_scope("loss"):
                loss, met = TF.loss_fn(cfg, params, lbatch, remat=remat,
                                       seg_hooks=hooks, top_hook=top_hook)
                if guard:
                    # fault injection rides the LOSS inside the
                    # differentiated function: autodiff propagates the
                    # NaN into this worker's entire gradient, exactly
                    # like a real fp blow-up on the device would
                    f = faultf[jax.lax.axis_index(waxes)]
                    loss = loss * jnp.where(f > 0, jnp.float32(jnp.nan),
                                            jnp.float32(1.0))
            return loss, met

        (loss, met), (agg, tgrads) = jax.value_and_grad(
            lfn, argnums=(0, 1), has_aux=True)(params, toks)
        # each token's cotangent is one_hot(n_selected) per barrier
        # call; gradient accumulation sums them over buckets and
        # scan iterations into one histogram over counts 0..m
        sel_hist = sum(jax.tree.leaves(tgrads))

        with jax.named_scope("optimizer"):
            new_params, new_opt = opt.update(agg, opt_state, params,
                                             step_idx)
            # fsdp-sharded leaves need a cross-worker psum; replicated
            # leaves are already global.
            from ..core.blocked import _fsdp_dim
            ss_f = jnp.float32(0)
            ss_r = jnp.float32(0)
            for g, s in zip(jax.tree.leaves(agg),
                            jax.tree.leaves(
                                pspecs, is_leaf=lambda x: isinstance(x, P))):
                ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if _fsdp_dim(s, waxes) is not None:
                    ss_f += ss
                else:
                    ss_r += ss
            gnorm = jnp.sqrt(jax.lax.psum(ss_f, waxes) + ss_r)
        # stats were psum'd before the (replicated) selection, so the
        # histogram is identical on every worker — no further
        # cross-worker reduction needed
        counts = jnp.arange(m + 1, dtype=jnp.float32)
        n_sel = (jnp.sum(counts * sel_hist)
                 / jnp.maximum(jnp.sum(sel_hist), 1.0))
        n_sel_min = jnp.argmax(sel_hist > 0).astype(jnp.float32)
        if guard:
            # per-worker finiteness, psum'd into a replicated [m]
            # vector — the supervisor's eviction signal.  Loss metrics
            # become ACTIVE-masked means with exact where-masking so
            # one NaN worker (faulted but not yet evicted, or evicted
            # but still computing) can't keep the run's loss NaN.
            idx = jax.lax.axis_index(waxes)
            ok_i = jnp.isfinite(loss).astype(jnp.float32)
            worker_ok = jax.lax.psum(
                jax.nn.one_hot(idx, m, dtype=jnp.float32) * ok_i, waxes)
            w = activef[idx] * ok_i
            denom = jnp.maximum(jax.lax.psum(w, waxes), 1.0)
            loss_m = jax.lax.psum(
                w * jnp.where(jnp.isfinite(loss), loss, 0.0), waxes) / denom
            ce_m = jax.lax.psum(
                w * jnp.where(jnp.isfinite(met["ce"]), met["ce"], 0.0),
                waxes) / denom
        else:
            loss_m = jax.lax.pmean(loss, waxes)
            ce_m = jax.lax.pmean(met["ce"], waxes)
        metrics = {
            "loss": loss_m,
            "ce": ce_m,
            "gnorm": gnorm,
            "n_selected": n_sel,
            "n_selected_min": n_sel_min,
        }
        if guard:
            metrics["worker_ok"] = worker_ok
        if cfg.is_moe:
            metrics["moe_pairs"] = jax.lax.psum(met["moe_pairs"], waxes)
            metrics["moe_load_max"] = jax.lax.pmax(met["moe_load_max"], waxes)
        return new_params, new_opt, metrics

    return step, pspecs, ospecs, bspecs


def _build_global_step(tcfg, mesh, opt, layout):
    """Auto-SPMD loss region + full-manual aggregation region +
    auto-SPMD optimizer update.

    The loss is a vmap over the worker axis of the batch — NO shard_map:
    a lax.scan (the layer stack) inside a partial-manual region trips
    XLA's manual-subgroup handling, and under plain jit GSPMD shards the
    vmapped compute over the worker axes and the tensor-parallel math
    over 'model' exactly as the serving paths do.  Only the aggregation,
    which needs real worker collectives, enters manual mode — over
    EVERY axis at once."""
    cfg = tcfg.model
    bcfg = tcfg.byzantine
    waxes = worker_axes(mesh, "global")
    maxes = tuple(a for a in mesh.axis_names if a not in waxes)
    wspec = tuple(waxes) if len(waxes) > 1 else waxes[0]
    m = n_workers(mesh, "global")
    defs = TF.param_defs(cfg)
    pspecs = PM.pspec_tree(defs, mesh, fsdp=False)
    ospecs = _opt_state_specs(tcfg.optimizer, pspecs)
    bspecs = batch_specs_for(cfg, waxes)
    remat = tcfg.remat == "block"
    is_pspec = lambda x: isinstance(x, P)
    elastic = bcfg.elastic
    guard = tcfg.recovery.guard
    extra = (P(),) if elastic else ()

    # full-manual aggregation region: worker collectives in any engine
    # layout lower cleanly; leaves arrive as [1, *model-local shard]
    gb_in = jax.tree.map(lambda s: P(wspec, *s), pspecs, is_leaf=is_pspec)

    @partial(shard_map, mesh=mesh, in_specs=(gb_in, P(), *extra),
             out_specs=(pspecs, P()),
             axis_names=set(mesh.axis_names), check_vma=False)
    def agg_region(gstack, key, *rest):
        activef = rest[0] if elastic else None
        local = jax.tree.map(lambda g: g.reshape(g.shape[1:]), gstack)
        local = threat.inject(local, key, bcfg, waxes,
                              leaf_specs=pspecs, model_axes=maxes,
                              active=activef)
        agg, st = robust_aggregate(local, bcfg, waxes, layout=layout,
                                   flatten_columns=True,
                                   model_axes=maxes, leaf_specs=pspecs,
                                   valid=activef)
        if st is not None:
            n_sel = jnp.sum(st.selected.astype(jnp.float32))
        elif elastic:
            n_sel = jnp.sum((activef > 0).astype(jnp.float32))
        else:
            n_sel = jnp.float32(m)
        return agg, n_sel

    def step(params, opt_state, batch, step_idx, key, *rest):
        activef = rest[0] if elastic else None
        faultf = rest[1] if guard else None

        if guard:
            # the fault flag multiplies the LOSS inside the
            # differentiated function, so autodiff turns one flag into
            # a fully-NaN per-worker gradient — a faithful stand-in
            # for an fp blow-up on that worker's device
            def wloss(p, wbatch, f):
                with jax.named_scope("loss"):
                    loss, met = TF.loss_fn(cfg, p, wbatch, remat=remat)
                    return loss * jnp.where(f > 0, jnp.float32(jnp.nan),
                                            jnp.float32(1.0)), met

            (loss, met), grads = jax.vmap(
                jax.value_and_grad(wloss, has_aux=True),
                in_axes=(None, 0, 0))(params, batch, faultf)
        else:
            def wloss(p, wbatch):
                with jax.named_scope("loss"):
                    return TF.loss_fn(cfg, p, wbatch, remat=remat)

            (loss, met), grads = jax.vmap(
                jax.value_and_grad(wloss, has_aux=True),
                in_axes=(None, 0))(params, batch)
        # pin the per-worker grad stack to [worker axes, *param sharding]
        # so the hand-off into the manual region inserts no resharding
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, P(wspec, *s))),
            grads, pspecs, is_leaf=is_pspec)
        with jax.named_scope("aggregate"):
            agg, n_sel = agg_region(grads, key,
                                    *((activef,) if elastic else ()))
        with jax.named_scope("optimizer"):
            new_params, new_opt = opt.update(agg, opt_state, params,
                                             step_idx)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(agg)))
        if guard:
            # active-masked finite means + the per-worker finiteness
            # vector (the supervisor's eviction signal); exact
            # where-masking keeps one NaN worker from poisoning the
            # run's loss metric forever
            worker_ok = jnp.isfinite(loss).astype(jnp.float32)
            w = (activef > 0).astype(jnp.float32) * worker_ok
            denom = jnp.maximum(jnp.sum(w), 1.0)
            loss_m = jnp.sum(
                w * jnp.where(jnp.isfinite(loss), loss, 0.0)) / denom
            ce_m = jnp.sum(
                w * jnp.where(jnp.isfinite(met["ce"]), met["ce"],
                              0.0)) / denom
        else:
            loss_m, ce_m = jnp.mean(loss), jnp.mean(met["ce"])
        metrics = {"loss": loss_m, "ce": ce_m,
                   "gnorm": gnorm,
                   "n_selected": n_sel, "n_selected_min": n_sel}
        if guard:
            metrics["worker_ok"] = worker_ok
        if cfg.is_moe:
            metrics["moe_pairs"] = jnp.sum(met["moe_pairs"])
            metrics["moe_load_max"] = jnp.max(met["moe_load_max"])
        return new_params, new_opt, metrics

    return step, pspecs, ospecs, bspecs


def build_train_step(tcfg: TrainConfig, mesh, jit: bool = True) -> StepBundle:
    """``jit=False`` returns the raw (unjitted) step callable — the
    static-analysis driver (``repro.launch.lint``) traces it with
    ``jax.make_jaxpr`` without a pjit wrapper around the whole step.

    When ``tcfg.byzantine`` is elastic (quorum/max_m set — DESIGN.md
    §Elastic) the returned step takes a sixth argument ``active`` ([m]
    0/1, who reached this round's quorum), defaulting to all-ones.  The
    mask is traced, so steps at m, m−2, m+2 active workers share ONE
    executable.  Passing ``active`` to a non-elastic step is an error —
    the fixed-m graphs would silently ignore it.

    With ``tcfg.recovery.guard`` (requires elastic) the step grows two
    more traced args — ``faults`` ([m] 0/1 grad-fault injection flags)
    and ``loss_ema`` (scalar, < 0 disarms the spike detector) — plus
    metrics ``worker_ok`` ([m] per-worker gradient finiteness),
    ``step_ok``, ``grad_finite`` and ``loss_spike``.  A non-finite or
    spiking step returns the INPUT params/opt state unchanged (in-jit
    hold); the host-side supervisor (faults/supervisor.py) reads the
    metrics and decides eviction / rollback."""
    opt = get_optimizer(tcfg)
    scope, layout = resolve_strategy(tcfg)
    bcfg = tcfg.byzantine
    rcfg = tcfg.recovery
    m = n_workers(mesh, scope)
    if rcfg.guard and not bcfg.elastic:
        raise ValueError(
            "recovery.guard requires an elastic ByzantineConfig (set "
            "quorum/max_m): eviction and hold are expressed through the "
            "traced active mask")
    if bcfg.elastic:
        if bcfg.max_m and bcfg.max_m != m:
            raise ValueError(
                f"ByzantineConfig.max_m={bcfg.max_m} does not match the "
                f"mesh's {m} worker slots for scope={scope!r}")
        if bcfg.quorum > m:
            raise ValueError(
                f"ByzantineConfig.quorum={bcfg.quorum} exceeds the mesh's "
                f"{m} worker slots for scope={scope!r}")
    build = _build_blocked_step if scope == "blocked" else _build_global_step
    inner, pspecs, ospecs, bspecs = build(tcfg, mesh, opt, layout)

    # n_active is attached HERE, outside the scope builders: the blocked
    # shard_map enumerates its metric keys in out_specs, so new
    # replicated metrics belong in this wrapper (DESIGN.md §Serve
    # telemetry schema rides on it)
    if rcfg.guard:
        # in-jit detection + hold (DESIGN.md §Faults): non-finite
        # aggregate, non-finite loss, or a loss spike vs the traced EMA
        # parks BOTH params and optimizer state on their old values —
        # one fused select per leaf, no host round-trip, and because
        # active/faults/loss_ema are all traced the guard costs zero
        # recompiles across fault churn.  jnp.where is an exact select:
        # holding against a NaN candidate tree is safe.
        def step(params, opt_state, batch, step_idx, key, active=None,
                 faults=None, loss_ema=None):
            act = (jnp.ones((m,), jnp.float32) if active is None
                   else jnp.asarray(active, jnp.float32))
            flt = (jnp.zeros((m,), jnp.float32) if faults is None
                   else jnp.asarray(faults, jnp.float32))
            # EMA sentinel: < 0 disarms the spike detector (first steps)
            ema = (jnp.float32(-1.0) if loss_ema is None
                   else jnp.asarray(loss_ema, jnp.float32))
            new_p, new_o, met = inner(params, opt_state, batch,
                                      step_idx, key, act, flt)
            grad_ok = jnp.isfinite(met["gnorm"])
            loss_ok = jnp.isfinite(met["loss"])
            spike = (ema > 0) & (met["loss"] > rcfg.spike_mult * ema)
            ok = grad_ok & loss_ok & ~spike
            held_p = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                  new_p, params)
            held_o = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                  new_o, opt_state)
            met = {**met, "n_active": jnp.sum(act),
                   "step_ok": ok.astype(jnp.float32),
                   "grad_finite": grad_ok.astype(jnp.float32),
                   "loss_spike": spike.astype(jnp.float32)}
            return held_p, held_o, met
    elif bcfg.elastic:
        def step(params, opt_state, batch, step_idx, key, active=None):
            act = (jnp.ones((m,), jnp.float32) if active is None
                   else jnp.asarray(active, jnp.float32))
            params, opt_state, met = inner(params, opt_state, batch,
                                           step_idx, key, act)
            return params, opt_state, {**met, "n_active": jnp.sum(act)}
    else:
        def step(params, opt_state, batch, step_idx, key, active=None):
            if active is not None:
                raise ValueError(
                    "active mask passed to a non-elastic step; set "
                    "ByzantineConfig.quorum (or max_m) to opt in")
            params, opt_state, met = inner(params, opt_state, batch,
                                           step_idx, key)
            return params, opt_state, {**met, "n_active": jnp.float32(m)}

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1))
    return StepBundle(step, pspecs, ospecs, bspecs, scope, layout)
