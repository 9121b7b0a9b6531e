#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, kind of window and per-layer metrics are the files of
``bench/`` named there (see ``bench/__init__.py``).  A run sets up (makes
the weights or inputs on the device from the seed, compiles and warms up
the cell's own shapes), measures for ``--seconds``, then compares what
the timed path produced with the plain reference.  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries the per-layer
metrics read from its trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` with
``--trace 1``) and, last, ``checks``: each number compared with its
limit.  The same numbers are the last lines of standard error.  A run
that finds no TPU, or fewer chips than the cell asks for, exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

if __package__ in (None, ""):
    # run as a script: import the benchmark as the package ``bench`` from
    # the checkout's root, not its modules from this directory (whose
    # trace.py would shadow the standard library's)
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import common, trace  # noqa: E402

TRACE_DIR = common.ROOT / ".bench_traces"


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(spec: dict, cell_name: str, setup_s: float, result: dict) -> dict:
    out = {}
    for mt in spec["end_to_end"]:
        if not applies(mt, cell_name):
            continue
        if mt["name"] == "setup_s":
            out["setup_s"] = {"value": setup_s, "unit": "s"}
            continue
        value, unit = result["metrics"][mt["name"]]
        if unit != mt["unit"]:
            raise ValueError(f"{mt['name']}: window reports {unit!r}, "
                             f"BENCHMARK.json says {mt['unit']!r}")
        out[mt["name"]] = {"value": value, "unit": unit}
    return out


def per_layer(spec: dict, cell_name: str, ctx: dict) -> dict:
    reported = set(end_to_end_names(spec, cell_name))
    out = {}
    for mt in spec["per_layer"]:
        if not applies(mt, cell_name) or mt["moves"] not in reported:
            continue
        value = common.layer_metric(mt["name"]).read(ctx)
        if value is not None:
            out[mt["name"]] = {"value": value, "unit": mt["unit"]}
    return out


def end_to_end_names(spec: dict, cell_name: str) -> list:
    return [m["name"] for m in spec["end_to_end"] if applies(m, cell_name)]


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, traced: bool, devices, extra: dict | None = None) -> dict:
    """Set up, measure, check; the result object (without printing).

    ``extra`` reaches the window's set-up (tests plant faults through
    it) and may name the trace's device plane and ops line for a
    backend other than the TPU."""
    import jax
    extra = dict(extra or {})
    win = common.window(traffic["window"])
    ctx = {"config": config, "traffic": traffic, "seed": seed,
           "devices": devices, **extra}
    t0 = time.perf_counter()
    state = win.setup(ctx)
    setup_s = time.perf_counter() - t0

    tdir = TRACE_DIR / cell["name"]
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        try:
            result = win.measure(state, seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        result = win.measure(state, seconds)
    peak_bytes = common.memory_peak(devices)

    numbers = win.check(state, result)
    del state
    # a number without a limit in the traffic file is not compared
    limits = traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
              if k in limits}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and result["failed"] == 0)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": result["attempted"],
           "failed": result["failed"]}
    if traced:
        summary = trace.summarize(
            trace.load(tdir), result["window_s"], span_names=win.SPANS,
            plane_prefix=extra.get("trace_plane", trace.TPU_PLANE),
            line_name=extra.get("trace_line", trace.OPS_LINE))
        shutil.rmtree(tdir, ignore_errors=True)
        lctx = {"cell": cell, "config": config, "traffic": traffic,
                "window": result, "trace": summary, "chips": len(devices),
                "peak": extra.get("peak") or common.peaks(dev.device_kind)}
        out["metrics"] = per_layer(spec, cell["name"], lctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["device"] = device
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    else:
        out["metrics"] = end_to_end(spec, cell["name"], setup_s, result)
        out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (common.ROOT / "src" / "repro").is_dir():
        print("bench: the program under test (src/repro) is not in this "
              "checkout; nothing was run", file=sys.stderr)
        return 2
    spec = common.benchmark()
    cell = common.cell(args.workload, spec)
    config = common.config(cell["config"])
    traffic = common.traffic(cell["traffic"])

    try:
        devices = common.require_chips(cell["chips"])
    except common.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    common.program_on_path()
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = run_cell(spec, cell, config, traffic, args.seed, args.seconds,
                   bool(args.trace), devices)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
