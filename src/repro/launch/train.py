"""Real training driver: distributed BrSGD on an actual device mesh.

On the CPU container this runs reduced configs on a small host-device
mesh (set JAX_NUM_CPU_DEVICES or XLA_FLAGS before launch to get more
than one device); on a TPU pod the same driver runs the full config on
``make_production_mesh()``.

  PYTHONPATH=src JAX_NUM_CPU_DEVICES=8 python -m repro.launch.train \
      --arch qwen3-0.6b --reduced --steps 20 --attack gaussian --alpha 0.25

Under the JAX profiler each step is a ``train`` step span holding the
host spans ``feed``, ``dispatch`` (``supervise`` with --supervise),
``log_sync`` (the metrics' readback), ``telemetry`` and ``checkpoint``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np


def build_mesh(spec: str | None):
    import jax
    from .mesh import make_mesh, make_production_mesh
    n = len(jax.devices())
    if spec == "production":
        return make_production_mesh()
    if spec:
        shape = tuple(int(x) for x in spec.split("x"))
        return make_mesh(shape, ("data", "model")[:len(shape)] if len(shape) <= 2
                         else ("pod", "data", "model"))
    # default: as much data-parallel as the host offers
    model = 2 if n % 2 == 0 and n > 2 else 1
    return make_mesh((n // model, model), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-per-worker", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default=None, help="e.g. 4x2, or 'production'")
    ap.add_argument("--aggregator", default="brsgd",
                    help="any rule registered in core.engine "
                         "(validated after parse, when jax loads)")
    ap.add_argument("--attack", default="none",
                    help="'none' or any attack registered in core.threat "
                         "(validated after parse, when jax loads; the "
                         "error message lists the live registry)")
    ap.add_argument("--alpha", type=float, default=0.0)
    ap.add_argument("--membership", default="prefix",
                    choices=["prefix", "random", "resample"],
                    help="byzantine-membership policy (core.threat)")
    ap.add_argument("--quorum", type=int, default=0,
                    help="fire aggregation once this many workers have "
                         "arrived (0 = synchronous full round); opts the "
                         "step into the elastic path (DESIGN.md §Elastic)")
    ap.add_argument("--straggle", default="none",
                    help="arrival-delay distribution dist[:scale], dist in "
                         "none|exp|pareto — e.g. 'exp:0.5' (data.pipeline."
                         "ArrivalSchedule)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the recovery supervisor (DESIGN.md "
                         "§Faults): in-step finite/spike guard, worker "
                         "eviction, bounded rollback to last_good.  "
                         "Implies the elastic path (quorum defaults to "
                         "the full worker count)")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--agg-layout", default="auto")
    ap.add_argument("--agg-scope", default="auto")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save an (atomic) checkpoint every N steps into "
                         "--ckpt-dir; 0 = final step only.  A serving "
                         "HotSwapper polling the same directory hot-swaps "
                         "each one live (DESIGN.md §Serve)")
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    from . import compile_cache
    compile_cache.enable()

    import jax
    import jax.numpy as jnp

    import dataclasses

    from ..checkpoint import ckpt
    from ..configs import (ByzantineConfig, RecoveryConfig, TrainConfig,
                           get_config)
    from ..core import engine, threat
    from ..data.pipeline import (ArrivalSchedule, LMWorkerPipeline,
                                 parse_straggle)
    from ..faults import Supervisor
    from ..launch.mesh import n_workers
    from ..models import params as PM
    from ..models import transformer as TF
    from ..serving import telemetry
    from ..training.step import build_train_step, resolve_strategy

    if args.aggregator not in engine.registered():
        ap.error(f"--aggregator {args.aggregator!r}: "
                 f"choose from {', '.join(engine.registered())}")
    if args.attack != "none" and args.attack not in threat.registered():
        ap.error(f"--attack {args.attack!r}: choose from none, "
                 f"{', '.join(threat.registered())}")
    try:
        straggle, straggle_scale = parse_straggle(args.straggle)
    except ValueError as e:
        ap.error(f"--straggle {args.straggle!r}: {e}")
    mesh = build_mesh(args.mesh)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bcfg = ByzantineConfig(aggregator=args.aggregator, attack=args.attack,
                           alpha=args.alpha, membership=args.membership)
    tcfg = TrainConfig(model=cfg, byzantine=bcfg, optimizer=args.optimizer,
                       lr=args.lr, agg_layout=args.agg_layout,
                       agg_scope=args.agg_scope, remat=args.remat)

    # elastic rounds: any of --quorum, a straggle distribution, or a
    # timing-scope attack drops the synchronous-round assumption.  The
    # worker-slot count is scope-dependent (blocked folds 'model' into
    # the worker set), so resolve the scope before sizing max_m.
    timing = (args.attack != "none"
              and threat.get_spec(args.attack).scope == "timing")
    elastic = (args.quorum > 0 or straggle != "none" or timing
               or args.supervise)
    sched = None
    if elastic:
        scope, _ = resolve_strategy(tcfg)
        m = n_workers(mesh, scope)
        quorum = args.quorum or m
        bcfg = dataclasses.replace(bcfg, max_m=m, quorum=quorum)
        tcfg = dataclasses.replace(tcfg, byzantine=bcfg)
        sched = ArrivalSchedule(m, quorum, straggle, straggle_scale,
                                byz=bcfg, seed=tcfg.seed)
    if args.supervise:
        tcfg = dataclasses.replace(tcfg,
                                   recovery=RecoveryConfig(guard=True))

    bundle = build_train_step(tcfg, mesh)
    # blocked scope folds every mesh axis (incl. 'model') into the
    # worker set, so the pipeline's worker count is scope-dependent
    m = n_workers(mesh, bundle.scope)
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} workers={m} "
          f"scope={bundle.scope} arch={cfg.name} "
          f"params={PM.count_params(TF.param_defs(cfg)):,}")
    psh, osh, bsh = bundle.shardings(mesh)
    key = jax.random.PRNGKey(tcfg.seed)
    params = jax.device_put(PM.init_params(TF.param_defs(cfg), key), psh)
    if args.optimizer == "adamw":
        z = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        opt_state = {"m": z(), "v": z()}
    elif args.optimizer == "momentum":
        opt_state = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    else:
        opt_state = ()

    pipe = LMWorkerPipeline(cfg, m, args.batch_per_worker, args.seq,
                            seed=tcfg.seed, byz=bcfg)
    sup = None
    if args.supervise:
        sup = Supervisor(bundle.step_fn, bcfg, tcfg.recovery, m,
                         ckpt_dir=args.ckpt_dir, like=params,
                         shardings=psh)
    span = jax.profiler.TraceAnnotation
    tokens_per_step = m * args.batch_per_worker * args.seq
    t_start = time.perf_counter()
    t_first = None
    history = []
    with mesh:
        for step in range(args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with span("feed"):
                    batch = {k: jax.device_put(jnp.asarray(v), bsh[k])
                             for k, v in pipe.batch(step).items()}
                n_active = m
                if sup is not None:
                    active = sched.active(step)
                    with span("supervise"):
                        params, opt_state, met = sup.run_step(
                            params, opt_state, batch, step,
                            jax.random.fold_in(key, step),
                            sched_active=active)
                    n_active = int(met["n_active"])
                elif sched is not None:
                    active = sched.active(step)
                    n_active = int(active.sum())
                    with span("dispatch"):
                        params, opt_state, met = bundle.step_fn(
                            params, opt_state, batch, jnp.int32(step),
                            jax.random.fold_in(key, step),
                            jnp.asarray(active))
                else:
                    with span("dispatch"):
                        params, opt_state, met = bundle.step_fn(
                            params, opt_state, batch, jnp.int32(step),
                            jax.random.fold_in(key, step))
                if step % args.log_every == 0 or step == args.steps - 1:
                    with span("log_sync"):
                        met = {k: v if isinstance(v, str) else float(v)
                               for k, v in met.items()}
                    history.append({"step": step, "n_active": n_active,
                                    **met})
                    act_s = (f" active={n_active}/{m}" if sched is not None
                             else "")
                    print(f"step {step:4d} loss={met['loss']:.4f} "
                          f"gnorm={met['gnorm']:.3f} "
                          f"selected={met['n_selected']:.1f}/{m} "
                          f"(bucket min {met['n_selected_min']:.0f})"
                          + act_s, flush=True)
                    if args.ckpt_dir:
                        # robustness telemetry beside the checkpoints:
                        # the server surfaces the aggregation stats the
                        # weights it serves were trained under
                        # (serving/telemetry)
                        with span("telemetry"):
                            telemetry.append_row(args.ckpt_dir, {
                                "step": step,
                                "gnorm": met["gnorm"],
                                "n_selected": met["n_selected"],
                                "n_selected_min": met["n_selected_min"],
                                "n_active": met["n_active"],
                                "quorum": bcfg.quorum or m,
                            })
                if (args.ckpt_dir and args.ckpt_every
                        and (step + 1) % args.ckpt_every == 0):
                    with span("checkpoint"):
                        if sup is not None:
                            sup.checkpoint(params, step + 1)
                        else:
                            ckpt.save(args.ckpt_dir, params, step=step + 1)
            if step == 0:
                # the first step compiles: timed apart from the rate
                jax.block_until_ready(params)
                t_first = time.perf_counter()

    jax.block_until_ready(params)
    t_end = time.perf_counter()
    line = f"done: {args.steps} steps"
    if t_first is not None:
        line += f"; first step (compile included) {t_first - t_start:.1f}s"
    if args.steps > 1:
        rate = (args.steps - 1) * tokens_per_step / (t_end - t_first)
        line += f"; steps 1..{args.steps - 1}: {rate:.0f} tok/s"
    print(line)
    if sup is not None:
        s = sup.summary()
        print(f"supervisor: holds={s['holds']} evictions={s['evictions']} "
              f"rollbacks={s['rollbacks']} "
              f"quorum_shrinks={s['quorum_shrinks']} "
              f"quorum_holds={s['quorum_holds']}")
    if args.ckpt_dir:
        p = pathlib.Path(args.ckpt_dir)
        with span("checkpoint"):
            if sup is not None:
                sup.checkpoint(params, args.steps)
            else:
                ckpt.save(str(p), params, step=args.steps)
        (p / "history.json").write_text(json.dumps(history, indent=1))
        print(f"checkpoint -> {p}")
    return history


if __name__ == "__main__":
    main()
