"""Paper §1/§2 complexity claim: BrSGD aggregation is O(md); Krum is
O(m²(d + log m)); coordinate-wise median via sort is O(dm log m).

We time every registered aggregator over a grid of (m, d) in the
``local`` layout, plus every (aggregator × {gather, a2a, blocked}) pair
under shard_map on an 8-device host mesh (subprocess — the main process
keeps the real device); ``blocked`` is the FSDP in-backward bucket path
(core.blocked) timed on one FSDP-sharded bucket.  The ``elastic``
layout rows time the masked quorum-round path
(``engine.aggregate_local(..., valid=act)`` at 75% active workers) on
the same (m, d) grid, so the elastic-vs-bulk overhead of the validity
masking is a committed, trackable number.  Raw wall-times are printed as CSV, the
scaling exponents are fitted (brsgd ~ m^a d^b with a ~ 1, b ~ 1; krum
grows ~ m² at fixed d), and every row is emitted to ``BENCH_agg.json``
at the repo root — stamped with backend/jax-version/git-rev metadata
(``benchmarks/check_bench.py`` validates the schema in CI) so the perf
trajectory of the fused statistics + select kernels is trackable across
PRs: ``--compare BASELINE`` prints per-(aggregator × layout) speedups
vs a previously committed file, and ``--compare OLD NEW`` diffs two
files without re-timing anything.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ByzantineConfig
from repro.core import aggregators as A, engine

from .common import time_fn

MS = [8, 16, 32, 64]
DS = [10_000, 40_000, 160_000]
D_DIST = 40_000          # distributed rows: one d, m = n_devices = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(REPO, "BENCH_agg.json")
SCHEMA = 2               # 2: added the "meta" stamp (check_bench.py)


def bench_meta() -> dict:
    """Provenance stamp for one benchmark run — enough to interpret a
    row months later: numbers from different backends or jax versions
    are not comparable."""
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO, capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        rev = "unknown"
    return {"backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "git_rev": rev,
            "date": datetime.date.today().isoformat()}

_DIST_SNIPPET = textwrap.dedent("""
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from repro.compat import P, shard_map
    from repro.configs.base import ByzantineConfig
    from repro.core.distributed import robust_aggregate
    from repro.launch.mesh import make_mesh

    m, d = 8, %d
    mesh = make_mesh((m,), ("data",))
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(m, d)).astype("f4"))

    def bench(fn, *args, reps=5, warmup=2):
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    from repro.core.blocked import _bucket_aggregate
    bspecs = {"g": P("data")}

    rows = []
    for name in %r:
        cfg = ByzantineConfig(aggregator=name, alpha=0.25)
        for layout in ("gather", "a2a"):
            @jax.jit
            @partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P())
            def agg(x):
                local = {"g": x.reshape(x.shape[1:])}
                return robust_aggregate(local, cfg, ("data",), layout)[0]["g"]
            us = bench(agg, g)
            rows.append({"aggregator": name, "layout": layout,
                         "m": m, "d": d, "us_per_call": us})

        # blocked scope: the FSDP in-backward bucket path, one bucket of
        # one [d] leaf sharded over the workers (output = local shard)
        @jax.jit
        @partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                 out_specs=P("data"))
        def bagg(x):
            local = {"g": x.reshape(x.shape[1:])}
            return _bucket_aggregate(local, bspecs, cfg, ("data",))[0]["g"]
        us = bench(bagg, g)
        rows.append({"aggregator": name, "layout": "blocked",
                     "m": m, "d": d, "us_per_call": us})
    print("JSON:" + json.dumps(rows))
""")


def _distributed_rows():
    """Rows of the 8-device host mesh, from a child process pinned to
    the CPU: these are host rows by design, and a child that went for an
    accelerator would contend with this process, which holds it.  A
    failed or timed-out child fails the whole run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "")
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env["PYTHONPATH"]
    code = _DIST_SNIPPET % (D_DIST, sorted(A.AGGREGATORS))
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=1200)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"distributed rows timed out after {e.timeout}s")
    if proc.returncode != 0:
        raise RuntimeError(
            f"distributed rows failed (rc {proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("JSON:"):
            return json.loads(line[5:])
    raise RuntimeError("distributed rows: the child printed no JSON line")


def _geomean(ratios, label: str = "") -> float:
    """Geometric mean over the POSITIVE, finite ratios only.

    A zero or sub-timer-resolution timing used to flow straight into
    np.log as -inf/NaN and silently corrupt the printed speedup and the
    committed elastic_overhead fit; bad rows are now dropped with a
    warning (and an all-bad group returns NaN, which check_bench.py
    rejects loudly)."""
    arr = np.asarray(list(ratios), dtype=float)
    keep = np.isfinite(arr) & (arr > 0)
    if not np.all(keep):
        print(f"# WARNING: {label or 'geomean'}: dropped "
              f"{int((~keep).sum())}/{arr.size} non-positive or "
              "non-finite timing ratios")
    if not np.any(keep):
        return float("nan")
    return float(np.exp(np.mean(np.log(arr[keep]))))


def compare(base: dict, cur: dict) -> None:
    """Print per-(aggregator × layout) speedup of ``cur`` over ``base``
    (geometric mean across the (m, d) grid points both files share)."""
    def keyed(rows):
        return {(r["aggregator"], r["layout"], r["m"], r["d"]):
                r["us_per_call"] for r in rows}
    b, c = keyed(base["rows"]), keyed(cur["rows"])
    shared = sorted(set(b) & set(c))
    if not shared:
        print("# compare: no shared (aggregator, layout, m, d) rows")
        return
    for meta_of, tag in ((base, "base"), (cur, "cur ")):
        mt = meta_of.get("meta", {})
        print(f"# {tag}: backend={mt.get('backend', '?')} "
              f"jax={mt.get('jax_version', '?')} "
              f"rev={mt.get('git_rev', '?')} date={mt.get('date', '?')}")
    groups: dict = {}
    for k in shared:
        if c[k] > 0:                    # guard the division itself too
            groups.setdefault(k[:2], []).append(b[k] / c[k])
    print("aggregator,layout,n_points,speedup_geomean")
    for (agg, layout), ratios in sorted(groups.items()):
        gm = _geomean(ratios, f"compare {agg}/{layout}")
        print(f"{agg},{layout},{len(ratios)},{gm:.2f}x")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare", nargs="+", metavar="BENCH_JSON",
                    help="one file: run, then print speedup vs it; "
                         "two files: diff OLD NEW without running")
    ap.add_argument("--out", default=BENCH_PATH,
                    help="output path (default: repo BENCH_agg.json)")
    args = ap.parse_args()

    if args.compare and len(args.compare) == 2:
        old, new = (json.load(open(p)) for p in args.compare)
        compare(old, new)
        return 0
    if args.compare and len(args.compare) > 2:
        ap.error("--compare takes one or two files")
    # load the baseline BEFORE the run: --out may overwrite the very
    # file being compared against (the committed-BENCH use case)
    baseline = json.load(open(args.compare[0])) if args.compare else None

    rng = np.random.default_rng(0)
    rows, times, times_e = [], {}, {}
    fns, efns = {}, {}
    for name in sorted(A.AGGREGATORS):
        cfg = ByzantineConfig(aggregator=name, alpha=0.25)
        fns[name] = jax.jit(lambda G, c=cfg: A.aggregate(G, c))
        # elastic rows: the masked quorum-round path at 75% active
        # (quorum must satisfy the static q > 2*floor(alpha*q) bound)
        efns[name] = jax.jit(lambda G, act, c=cfg, n=name: engine
                             .aggregate_local(G, c, valid=act,
                                              spec=engine.get_spec(n)))

    print("aggregator,layout,m,d,us_per_call")
    for m in MS:
        for d in DS:
            G = jnp.asarray(rng.normal(size=(m, d)).astype("f4"))
            act = jnp.asarray(
                (np.arange(m) < int(0.75 * m)).astype("f4"))
            for name, fn in fns.items():
                us = time_fn(fn, G)
                times[(name, m, d)] = us
                rows.append({"aggregator": name, "layout": "local",
                             "m": m, "d": d, "us_per_call": us})
                print(f"{name},local,{m},{d},{us:.1f}", flush=True)
                ue = time_fn(efns[name], G, act)
                times_e[(name, m, d)] = ue
                rows.append({"aggregator": name, "layout": "elastic",
                             "m": m, "d": d, "us_per_call": ue})
                print(f"{name},elastic,{m},{d},{ue:.1f}", flush=True)

    for r in _distributed_rows():
        rows.append(r)
        print(f"{r['aggregator']},{r['layout']},{r['m']},{r['d']},"
              f"{r['us_per_call']:.1f}", flush=True)

    # scaling fits (log-log least squares)
    fits = {}
    for name in ("brsgd", "mean"):
        xs, ys = [], []
        for (n, m, d), us in times.items():
            if n == name and np.isfinite(us) and us > 0:
                xs.append([np.log(m), np.log(d), 1.0])
                ys.append(np.log(us))
        coef, *_ = np.linalg.lstsq(np.asarray(xs), np.asarray(ys), rcond=None)
        fits[name] = {"m_exp": float(coef[0]), "d_exp": float(coef[1])}
        print(f"# {name} scaling: time ~ m^{coef[0]:.2f} * d^{coef[1]:.2f}")

    # elastic-vs-bulk overhead: the masked path divided by the bulk
    # local path, geometric mean over the (m, d) grid per aggregator
    overhead = {}
    for name in sorted(A.AGGREGATORS):
        ratios = [times_e[k] / times[k] for k in times
                  if k[0] == name and k in times_e and times[k] > 0]
        overhead[name] = _geomean(ratios, f"{name} elastic/local")
        print(f"# {name} elastic/local overhead: x{overhead[name]:.2f}")

    # krum m-scaling at fixed d (expect ~quadratic at large m)
    d = DS[-1]
    r64_16 = times[("krum", 64, d)] / times[("krum", 16, d)]
    rb = times[("brsgd", 64, d)] / times[("brsgd", 16, d)]
    ok = rb < (r64_16 + 1) / 2 or rb < 8
    print(f"# m 16->64 (4x): krum x{r64_16:.1f} (O(m^2)->16x), "
          f"brsgd x{rb:.1f} (O(m)->4x)")
    print(f"# CLAIM brsgd O(md): {'PASS' if ok else 'FAIL'}")

    out = {"schema": SCHEMA, "meta": bench_meta(), "rows": rows,
           "fits": fits, "elastic_overhead": overhead,
           "krum_ratio_16_to_64": float(r64_16),
           "brsgd_ratio_16_to_64": float(rb), "claim_pass": bool(ok)}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {os.path.normpath(args.out)} ({len(rows)} rows)")
    if baseline is not None:
        compare(baseline, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
