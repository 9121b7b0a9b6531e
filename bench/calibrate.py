#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip, at
a cell's own size, in one process:

  python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
      [--control-seeds 21,22,23]

For every ``--seeds`` seed: the numbers that a run compares, from the
program's own timed path against the plain reference (the lower
readings).  For every ``--control-seeds`` seed: the same numbers from
the control (the reference in the precision below the configuration's,
put in the program's place) and from each fault the cell can have,
planted in the reference put in the program's place (the upper
readings).  One JSON line per reading on standard output.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

if __package__ in (None, ""):
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import common  # noqa: E402


def _emit(kind: str, seed: int, numbers: dict) -> None:
    print(json.dumps({"kind": kind, "seed": seed, **numbers}), flush=True)


def train_readings(ctx: dict, seeds, control_seeds) -> None:
    import jax
    import jax.numpy as jnp
    win = common.window("train")
    run = win.Run(ctx)
    B, m = ctx["traffic"]["batch_per_worker"], ctx["traffic"]["m"]
    for seed in sorted(set(seeds) | set(control_seeds)):
        run.start(seed)
        prog = run.first_steps()
        run.free()
        want = run.reference()
        if seed in seeds:
            _emit("program", seed, win.compare(prog, want))
        if seed in control_seeds:
            low = run.reference(precision=jax.lax.Precision.DEFAULT,
                                dtype=jnp.bfloat16)
            _emit("control_bf16", seed, win.compare(low, want))
            if m * B > 1:
                half = run.reference(keep_rows=m * B // 2)
                _emit("fault_half_batch", seed, win.compare(half, want))
            if m > 1:
                alone = run.reference(keep_rows=B)
                _emit("fault_exchange_left_out", seed, win.compare(alone, want))
            unchanged = dict(want, change=[0.0] * len(want["change"]))
            _emit("fault_state_unchanged", seed, win.compare(unchanged, want))


def agg_readings(ctx: dict, seeds, control_seeds) -> None:
    import jax.numpy as jnp
    import numpy as np
    win = common.window("agg")
    tr = ctx["traffic"]
    entry = win.entry_for(tr)
    quant = {"bfloat16": jnp.float8_e4m3fn}[tr["dtype"]]
    for seed in sorted(set(seeds) | set(control_seeds)):
        state = win.setup({**ctx, "seed": seed})
        G, n_byz = state["G"], state["n_byz"]
        kept = win.reference_kept(state)
        if seed in seeds:
            out, sel = entry(G)
            sel = np.asarray(sel, bool)
            _emit("program", seed, {
                "selection_mismatches": int((sel != kept).sum()),
                "byzantine_selected": int(sel[:n_byz].sum()),
                "aggregate_max_gap": win.ref.max_gap(G, kept, out)})
            del out
        if seed in control_seeds:
            out, _sel = entry(G)
            # one answer altered where it is produced, by the grid's step
            out = out.at[int(np.asarray(common.np_rng(seed, 5).integers(
                0, out.shape[0])))].add(1.0 / win.GRID)
            _emit("fault_answer_altered", seed, {
                "aggregate_max_gap": win.ref.max_gap(G, kept, out)})
            del out
            kept_q = win.reference_kept(state, quant=quant)
            _emit("control_fp8", seed, {
                "selection_mismatches": int((kept_q != kept).sum()),
                "byzantine_selected": int(kept_q[:n_byz].sum()),
                "aggregate_max_gap": win.ref.control_gap(G, kept, kept_q, quant)})
            # half of the workers' rows left out, the mean taken over the
            # rest (the reference put in the program's place)
            half = np.zeros_like(kept)
            half[len(kept) // 2:] = kept[len(kept) // 2:]
            if not half.any():
                half[len(kept) // 2:] = True
            _emit("fault_half_rows", seed, {
                "selection_mismatches": int((half != kept).sum()),
                "byzantine_selected": int(half[:n_byz].sum()),
                "aggregate_max_gap": win.ref.control_gap(G, kept, half, None)})
        del state, G


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
    common.program_on_path()
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        ctx["devices"] = common.require_chips(cell["chips"])
    except common.NoChip as e:
        print(f"calibrate: {e}; nothing was run", file=sys.stderr)
        return 2
    kind = ctx["traffic"]["window"]
    {"train": train_readings, "agg": agg_readings}[kind](ctx, seeds, control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
