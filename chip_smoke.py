#!/usr/bin/env python3
"""Smoke run of robust training and serving on TPU, through the entry
points a user calls, at the published widths of qwen3-0.6b (28 layers,
d_model 1024, d_ff 3072, GQA 16/8 heads of 128, vocab 151,936; random
weights from a seed).

  python chip_smoke.py            one chip, in order:
      train        repro.launch.train.main: brsgd, a few steps, finite
                   losses, a final checkpoint in a scratch directory
      kernel       the same step lowered and compiled: its HLO carries
                   the Pallas stats kernel (tpu_custom_call)
      aggregation  engine.aggregate_local at m=8 over real leaves
                   (embedding, a stacked MLP weight, a norm vector) with
                   a quarter of the rows attacked: the compiled kernels
                   against the jnp reference for brsgd, median and
                   trimmed_mean
      serve        repro.launch.serve.main --serve-loop from that
                   checkpoint: every request answered, one decode compile
  python chip_smoke.py --chips 4  four chips, global scope, m=4 workers
                   (--mesh 4x1): brsgd, mean and brsgd under a gaussian
                   attack; nothing else.

Exits non-zero, with no result line, when JAX finds no TPU or any
phase fails.  The last line of stdout is one JSON object naming the
device.  This is a smoke run: its times are not benchmark metrics.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

ARCH = "qwen3-0.6b"
STEPS = 3
AGG_M = 8
AGG_ALPHA = 0.25
AGG_LEAVES = ("embed", "seg_0/mlp/w_gate", "final_norm")
AGG_GRID = 256.0
# kernel vs reference in f32: the two sum rows in different orders
AGG_RTOL = AGG_ATOL = 1e-5
# two compiled programs may fuse the same forward differently
LOSS_RTOL = 1e-5
KERNEL_MARK = "tpu_custom_call"
GIB = 2 ** 30


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, JAX found {len(devs)}")
    return devs


def timed(timings: dict, name: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    timings[name] = time.perf_counter() - t0
    log(f"[{name}] {timings[name]:.3f} s")
    return out


def train(argv: list) -> list:
    from repro.launch import train as T
    hist = T.main(["--arch", ARCH, "--steps", str(STEPS), *argv])
    losses = [h["loss"] for h in hist]
    assert len(hist) == STEPS and all(map(math.isfinite, losses)), losses
    return hist


def compiled_step(aggregator: str, mesh_spec=None, attack="none",
                  alpha=0.0):
    """Lower and compile the step ``train.main`` builds for these flags
    (its defaults otherwise) -> (compiled, lower s, compile s)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ByzantineConfig, TrainConfig, get_config
    from repro.launch.mesh import n_workers
    from repro.launch.train import build_mesh
    from repro.models import params as PM
    from repro.models import transformer as TF
    from repro.training.step import build_train_step

    mesh = build_mesh(mesh_spec)
    cfg = get_config(ARCH)
    tcfg = TrainConfig(model=cfg, byzantine=ByzantineConfig(
        aggregator=aggregator, attack=attack, alpha=alpha))
    bundle = build_train_step(tcfg, mesh)
    m = n_workers(mesh, bundle.scope)
    psh, _osh, bsh = bundle.shardings(mesh)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=s),
        PM.abstract_params(TF.param_defs(cfg)), psh)
    rep = NamedSharding(mesh, P())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    lowered = bundle.step_fn.lower(
        params, {"m": params, "v": params},
        {"tokens": jax.ShapeDtypeStruct((m, 2, 128), jnp.int32,
                                        sharding=bsh["tokens"])},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, t1 - t0, time.perf_counter() - t1


def kernel_check(timings: dict) -> None:
    compiled, t_lower, t_compile = compiled_step("brsgd")
    timings["step_lower"], timings["step_compile"] = t_lower, t_compile
    ma = compiled.memory_analysis()
    log(f"step: lower {t_lower:.3f} s, compile {t_compile:.3f} s; "
        f"arguments {ma.argument_size_in_bytes / GIB:.3f} GiB, "
        f"temporaries {ma.temp_size_in_bytes / GIB:.3f} GiB")
    n = compiled.as_text().count(KERNEL_MARK)
    log(f"step HLO: {n} Pallas kernel call(s)")
    assert n > 0, "the train step runs no Pallas kernel"


def aggregation() -> None:
    """Compiled kernels vs the jnp reference on real qwen3-0.6b leaf
    sizes, m=8 workers with a quarter of them attacked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ByzantineConfig, get_config
    from repro.core import engine, threat
    from repro.models import params as PM
    from repro.models import transformer as TF

    defs = TF.param_defs(get_config(ARCH))
    sizes = {jax.tree_util.keystr(p, simple=True, separator="/"):
             math.prod(d.shape)
             for p, d in jax.tree_util.tree_flatten_with_path(
                 defs, is_leaf=PM.is_param_def)[0]}
    attack = ByzantineConfig(attack="gaussian", alpha=AGG_ALPHA)
    byz = np.asarray(threat.membership_mask(attack, AGG_M))   # prefix

    @functools.partial(jax.jit, static_argnums=1)
    def worker_grads(key, d: int):
        # honest rows: a shared direction plus per-worker noise
        k1, k2, k3 = jax.random.split(key, 3)
        G = (jax.random.normal(k1, (1, d)) +
             0.1 * jax.random.normal(k2, (AGG_M, d)))
        G = threat.apply_dense(G, k3, attack)
        # on a 2^-8 grid (|g| < 2^13) every column sum of m rows is exact
        # in f32 in any order: kernel and reference then compare rows to
        # bit-identical column means, so a selection that differs is a
        # fault, not a rounding tie at the mean
        return jnp.round(G * AGG_GRID) / AGG_GRID

    rules = {"brsgd": {}, "median": {}, "trimmed_mean": {"trim_frac": 0.25}}
    for li, leaf in enumerate(AGG_LEAVES):
        d = sizes[leaf]
        G = worker_grads(jax.random.PRNGKey(li), d)
        for name, kw in rules.items():
            cfg = ByzantineConfig(aggregator=name, alpha=AGG_ALPHA, **kw)
            got, st_k = engine.aggregate_local(G, cfg, use_pallas=True,
                                               return_state=True)
            want, st_r = engine.aggregate_local(G, cfg, use_pallas=False,
                                                return_state=True)
            err = float(jnp.max(jnp.abs(got - want)))
            n_bad = int(jnp.sum(jnp.abs(got - want)
                                > AGG_ATOL + AGG_RTOL * jnp.abs(want)))
            line = (f"aggregation {name:12s} {leaf:18s} d={d:>11,d} "
                    f"max|kernel-ref|={err:.3e}")
            if st_k is not None:
                sel_k = np.asarray(st_k.selected)
                sel_r = np.asarray(st_r.selected)
                line += f" selected={sel_k.astype(int).tolist()}"
                assert (sel_k == sel_r).all(), (name, leaf, sel_k, sel_r)
                assert not (sel_k & byz).any(), (name, leaf, "picked byz")
            log(line)
            assert n_bad == 0, (name, leaf, n_bad, err)
        del G


def serve(ckpt_dir: str) -> None:
    from repro.launch import serve as S
    n_req = 8
    loop = S.main(["--arch", ARCH, "--serve-loop", "--ckpt-dir", ckpt_dir,
                   "--requests", str(n_req), "--max-batch", "4"])
    assert loop.swapper.loaded_step == STEPS, loop.swapper.loaded_step
    assert len(loop.done) == n_req, sorted(loop.done)
    assert loop.decode_compiles() == 1, loop.decode_compiles()


def log_memory(devs, label: str) -> list:
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    log(f"peak device memory after {label}: "
        + ", ".join(f"{p / GIB:.3f} GiB" for p in peaks))
    return peaks


def one_chip(devs, timings: dict) -> None:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as ckpt_dir:
        timed(timings, "train", train,
              ["--aggregator", "brsgd", "--ckpt-dir", ckpt_dir])
        log_memory(devs[:1], "train")
        timed(timings, "kernel", kernel_check, timings)
        timed(timings, "aggregation", aggregation)
        log_memory(devs[:1], "aggregation")
        timed(timings, "serve", serve, ckpt_dir)


def four_chips(devs, timings: dict) -> None:
    """Global scope, m=4 workers on four chips (``--mesh 4x1``)."""
    import jax

    from repro.configs import get_config
    from repro.models import params as PM
    from repro.models import transformer as TF

    base = ["--mesh", "4x1"]
    runs = {
        "brsgd": ["--aggregator", "brsgd"],
        "mean": ["--aggregator", "mean"],
        "brsgd_gaussian": ["--aggregator", "brsgd", "--attack", "gaussian",
                           "--alpha", "0.25"],
    }
    hist = {k: timed(timings, f"train_{k}", train, base + v)
            for k, v in runs.items()}
    l_b, l_m = hist["brsgd"][0]["loss"], hist["mean"][0]["loss"]
    assert math.isclose(l_b, l_m, rel_tol=LOSS_RTOL), (l_b, l_m)
    sel = [h["n_selected"] for h in hist["brsgd_gaussian"]]
    log(f"step-0 loss brsgd={l_b!r} mean={l_m!r}; "
        f"n_selected under attack={sel}")
    assert all(s < 4 for s in sel), sel
    # f32 params + Adam m and v, replicated over the four workers
    state = 12 * PM.count_params(TF.param_defs(get_config(ARCH)))
    peaks = log_memory(devs[:4], "training")
    assert all(p >= 0.9 * state for p in peaks), (peaks, state)

    compiled, t_lower, t_compile = timed(
        timings, "kernel", compiled_step, "brsgd", "4x1")
    log(f"step: lower {t_lower:.3f} s, compile {t_compile:.3f} s")
    text = compiled.as_text()
    counts = {k: text.count(k) for k in
              ("all-to-all", "all-gather", KERNEL_MARK)}
    log(f"step HLO: {counts}")
    assert all(counts.values()), counts
    params_sh = compiled.input_shardings[0][0]
    n_dev = {len(s.device_set) for s in jax.tree.leaves(params_sh)}
    assert n_dev == {4}, n_dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    devs = require_tpu(args.chips)
    kind = devs[0].device_kind
    log(f"device: {devs[0].platform} {kind} x{len(devs)}")
    timings: dict = {}
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(devs, timings)
    timings["total"] = time.perf_counter() - t0
    log("timings (s): " + json.dumps(timings))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
