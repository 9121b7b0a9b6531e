"""Serving driver: fused one-dispatch prefill + greedy decode, and the
continuous-batching serve loop with hot-swapped checkpoints.

Single-shot (fixed batch, shared prompt length):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --prompt-len 32 --gen 16 --batch 4

Continuous batching + hot swap + /metrics (DESIGN.md §Serve):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --serve-loop --requests 8 --max-batch 4 --ckpt-dir runs/ck \
      --metrics-out metrics.txt
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def run_serve_loop(args, cfg):
    """Continuous batching over a synthetic request stream; params come
    from the newest checkpoint under --ckpt-dir (hot-swapped live) or a
    fresh init when no directory is given.  Returns the drained
    ServeLoop (answers in ``.done``, compile counters, metrics)."""
    import jax

    from ..checkpoint import ckpt
    from ..models import params as PM
    from ..models import transformer as TF
    from ..serving import HotSwapper, ServeLoop, latest_row

    key = jax.random.PRNGKey(args.seed)
    like = PM.init_params(TF.param_defs(cfg), key)
    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.ckpt_dir:
        swapper = HotSwapper(args.ckpt_dir, like=like)
        loop = ServeLoop(cfg, args.max_batch, max_len, swapper=swapper)
        print(f"serving checkpoint step {swapper.loaded_step} "
              f"from {args.ckpt_dir}")
    else:
        loop = ServeLoop(cfg, args.max_batch, max_len, params=like)

    rng = np.random.RandomState(args.seed)
    for _ in range(args.requests):
        plen = rng.randint(max(2, args.prompt_len // 2), args.prompt_len + 1)
        loop.submit(rng.randint(0, cfg.vocab, size=plen), max_new=args.gen)
    t0 = time.time()
    done = loop.run()
    dt = time.time() - t0
    assert len(done) == args.requests, "dropped requests"
    n_tok = sum(len(v) for v in done.values())
    print(f"arch={cfg.name} requests={args.requests} "
          f"max_batch={args.max_batch} tokens={n_tok} "
          f"({n_tok / max(dt, 1e-9):.0f} tok/s) steps={loop.steps} "
          f"decode_compiles={loop.decode_compiles()}")
    if loop.swapper:
        print(f"swaps={loop.swapper.swap_count} "
              f"(serving step {loop.swapper.loaded_step})")
    train_row = latest_row(args.ckpt_dir) if args.ckpt_dir else None
    metrics = loop.metrics.render(train_row)
    print(metrics, end="")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(metrics)
        print(f"metrics -> {args.metrics_out}")
    return loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length; default prompt+gen")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-loop", action="store_true",
                    help="continuous-batching scheduler instead of the "
                         "fixed-batch single shot")
    ap.add_argument("--requests", type=int, default=8,
                    help="[serve-loop] synthetic request count")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="[serve-loop] decode slot count")
    ap.add_argument("--ckpt-dir", default=None,
                    help="[serve-loop] serve (and hot-swap) checkpoints "
                         "from this directory")
    ap.add_argument("--metrics-out", default=None,
                    help="[serve-loop] write the /metrics dump here")
    args = ap.parse_args(argv)

    from . import compile_cache
    compile_cache.enable()

    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..models import params as PM
    from ..models import transformer as TF

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.serve_loop:
        return run_serve_loop(args, cfg)

    max_len = args.max_len or (args.prompt_len + args.gen)
    key = jax.random.PRNGKey(args.seed)
    params = PM.init_params(TF.param_defs(cfg), key)
    B = args.batch
    prompt = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab)
    dtype = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16

    prefill = jax.jit(lambda p, t, c: TF.prefill_cache(cfg, p, t, c),
                      donate_argnums=(2,))
    decode = jax.jit(lambda p, c, t, pos: TF.decode_step(cfg, p, c, t, pos),
                     donate_argnums=(1,))

    # fused prefill: ONE dispatch writes the whole prompt's KV/state
    # (the seed teacher-forced the decode step per token — O(prompt_len)
    # dispatches)
    cache = TF.init_cache(cfg, B, max_len, dtype)
    t0 = time.time()
    logits, cache = prefill(params, prompt, cache)
    logits = jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    toks = []
    t0 = time.time()
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(args.gen):
        toks.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, cache, tok,
                               jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits.reshape(B, -1), axis=-1)[:, None].astype(jnp.int32)
    t_gen = time.time() - t0

    gen = np.stack(toks, axis=1)
    print(f"arch={cfg.name} B={B} prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill: {t_prefill:.2f}s ({B * args.prompt_len / t_prefill:.0f} tok/s, 1 dispatch)")
    print(f"decode : {t_gen:.2f}s ({B * args.gen / max(t_gen, 1e-9):.0f} tok/s)")
    print("sample tokens:", gen[0][:12].tolist())
    assert np.isfinite(np.asarray(logits)).all(), "NaN in serving logits"
    return gen


if __name__ == "__main__":
    main()
