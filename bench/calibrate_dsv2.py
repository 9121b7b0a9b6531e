#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for a cell of
the ``train_dsv2`` window, on the chip at the cell's own size, in one
process:

  python3 bench/calibrate_dsv2.py --workload <cell> --seeds 11,12,... \
      [--control-seeds 21,22,23]

For every ``--seeds`` seed: the numbers that a run compares, from the
program's own timed path against the plain reference (the lower
readings).  For every ``--control-seeds`` seed, put in the program's
place against the reference: the control (the reference in bfloat16)
and the reference with each planted fault (the upper readings):

  fault_renormalised  top-k weights renormalised to sum to 1
  fault_top_k_less_one  one expert fewer per token
  fault_plain_rope    plain RoPE in place of YaRN
  fault_half_batch    half of the batch: the first half of each row's
                      positions (a batch of one row has no half of rows)
  fault_state_unchanged  parameters left as they were

One JSON line per reading on standard output, as ``calibrate.py``
prints them.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

if __package__ in (None, ""):
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import common  # noqa: E402
from bench.calibrate import _emit  # noqa: E402


def faults(cfg: dict) -> dict:
    return {"fault_renormalised": dict(cfg, norm_topk_prob=True),
            "fault_top_k_less_one": dict(cfg, num_experts_per_tok=cfg["num_experts_per_tok"] - 1),
            "fault_plain_rope": dict(cfg, rope_scaling=None)}


def readings(ctx: dict, seeds, control_seeds) -> None:
    import jax
    import jax.numpy as jnp
    win = common.window("train_dsv2")
    run = win.Run(ctx)
    cfg = run.cfg
    for seed in sorted(set(seeds) | set(control_seeds)):
        run.start(seed)
        prog = run.first_steps()
        run.free()
        want = run.reference()
        if seed in seeds:
            _emit("program", seed, win.compare(prog, want))
        if seed not in control_seeds:
            continue
        low = run.reference(precision=jax.lax.Precision.DEFAULT, dtype=jnp.bfloat16)
        _emit("control_bf16", seed, win.compare(low, want))
        for kind, bad in faults(cfg).items():
            run.cfg = bad              # the reference computes run.cfg
            try:
                got = run.reference()
            finally:
                run.cfg = cfg
            _emit(kind, seed, win.compare(got, want))
        ring, shape = run.ring, run.shape
        run.ring = [r[..., :shape[-1] // 2] for r in ring]
        run.shape = (*shape[:-1], shape[-1] // 2)
        try:
            half = run.reference()
        finally:
            run.ring, run.shape = ring, shape
        _emit("fault_half_batch", seed, win.compare(half, want))
        unchanged = dict(want, change=[0.0] * len(want["change"]))
        _emit("fault_state_unchanged", seed, win.compare(unchanged, want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    cell = common.cell(args.workload)
    ctx = {"config": common.config(cell["config"]),
           "traffic": common.traffic(cell["traffic"])}
    if ctx["traffic"]["window"] != "train_dsv2":
        print("calibrate_dsv2: the cell's window is not train_dsv2 "
              "(bench/calibrate.py calibrates the others)", file=sys.stderr)
        return 2
    common.program_on_path()
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        ctx["devices"] = common.require_chips(cell["chips"])
    except common.NoChip as e:
        print(f"calibrate_dsv2: {e}; nothing was run", file=sys.stderr)
        return 2
    readings(ctx, seeds, control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
