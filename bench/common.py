"""Helpers shared by the harness, its windows and its tests: finding the
files of a cell by name, the chip check, seeds and the table of peaks."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"


def program_on_path() -> None:
    """Make the program under test (``src/repro``) importable."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return read_json(SPEC)


def cell(name: str, spec: dict | None = None) -> dict:
    spec = benchmark() if spec is None else spec
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in {SPEC.name}; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def config(name: str) -> dict:
    return read_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return read_json(BENCH / "workloads" / f"{name}.json")


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by path (window kinds and metric
    readers are found by name, and their names may hold dots)."""
    mod_name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                   .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def window(kind: str):
    return load_module(BENCH / "windows" / f"{kind}.py")


def layer_metric(name: str):
    return load_module(BENCH / "layer_metrics" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  A device that is not in the table is
    an error, never a default."""
    table = read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


class NoChip(RuntimeError):
    pass


def require_chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX found {len(devs)}")
    return devs[:n]


def prng_key(seed: int, *tags: int):
    """A JAX key from a seed of any size (``PRNGKey`` keeps only the low
    32 bits), with ``tags`` folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


def np_rng(seed: int, *tags: int):
    import numpy as np
    return np.random.default_rng([seed, *tags])


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend does not report it)."""
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_) if peaks_ else 0
