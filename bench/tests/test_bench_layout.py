"""BENCHMARK.json and the files of bench/ that it names: every cell, its
configuration, traffic mix, window kind and metrics exist and keep the
benchmark's rules, and a cell, a configuration or a per-layer metric is
added by adding files alone."""
import json
import re
import shutil

import jax
import pytest

from bench import common, run
from bench.tests.conftest import TINY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = common.benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w for w in SPEC["command"])
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_entries():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    assert [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]


def test_reduced_names_every_changed_key():
    for c in SPEC["configs"]:
        f = common.config(c["name"])
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert sorted(f["published"]) == sorted(c["reduced"])
        for k, v in f["published"].items():
            assert f[k] != v


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_exist(cell_name):
    """Every cell names a configuration, a traffic mix, a window kind and
    metrics that exist; each reports setup_s, one more end-to-end metric
    and one per-layer metric."""
    w = common.cell(cell_name)
    cfg = common.config(w["config"])
    tr = common.traffic(w["traffic"])
    win = common.window(tr["window"])
    for fn in ("setup", "measure", "check"):
        assert callable(getattr(win, fn))
    assert tr["limits"] and all(v >= 0 for v in tr["limits"].values())
    assert cfg["name"] == w["config"]
    e2e = run.end_to_end_names(SPEC, cell_name)
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"]
             if run.applies(m, cell_name) and m["moves"] in e2e]
    assert layer
    for m in layer:
        assert callable(common.layer_metric(m["name"]).read)


def test_every_traffic_file_names_a_window_that_exists():
    for path in sorted((common.BENCH / "workloads").glob("*.json")):
        tr = common.read_json(path)
        assert (common.BENCH / "windows" / f"{tr['window']}.py").is_file(), path
        assert tr["limits"], path


@pytest.mark.parametrize("cell_name", CELLS)
def test_no_chip_exits_nonzero_without_a_result(cell_name, capsys):
    """On the CPU backend the harness finds no TPU: exit code 2, nothing
    on standard output."""
    assert run.main(["--workload", cell_name, "--seed", str(2**31 + 3),
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_a_cell_is_added_by_files_alone(tmp_path, monkeypatch, cpu_extra):
    """A new configuration, traffic mix, per-layer metric and cell, added
    as files and BENCHMARK.json entries only, run end to end."""
    bench = tmp_path / "bench"
    shutil.copytree(common.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = dict(common.config("qwen3-0.6b"), name="tiny-test", **TINY)
    (bench / "configs" / "tiny-test.json").write_text(json.dumps(cfg))
    tr = dict(common.traffic("agg-brsgd-m8-gauss"), m=4)
    (bench / "workloads" / "agg-brsgd-m4-test.json").write_text(json.dumps(tr))
    (bench / "layer_metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['window']['rounds'])\n")
    spec = json.loads(json.dumps(SPEC))
    cell = {"name": "agg-tiny-test", "config": "tiny-test",
            "traffic": "agg-brsgd-m4-test", "chips": 1, "why": "test"}
    spec["workloads"].append(cell)
    spec["end_to_end"][1]["workloads"].append("agg-tiny-test")
    spec["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                              "better": "higher", "source": "program_counter",
                              "layer": "aggregation engine",
                              "moves": "agg_round_ms",
                              "workloads": ["agg-tiny-test"]})
    monkeypatch.setattr(common, "BENCH", bench)
    out = run.run_cell(spec, cell, common.config("tiny-test"),
                       common.traffic("agg-brsgd-m4-test"), 3, 0.3, True,
                       jax.devices(), cpu_extra)
    assert out["correct"] is True
    assert out["metrics"]["rounds_seen"]["value"] == out["attempted"] > 0
    assert out["device"]["busy_s"] > 0
    assert list(out)[-1] == "checks"
