"""The step's device time by layer (``bench/scopes.py``): the mapping of
an instruction's scope path to a layer, the times read from events made
by hand, and the new metrics read from a traced tiny train cell on the
CPU, on one device and on four."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import common, run, scopes
from bench.tests.conftest import tiny_config, tiny_traffic
from bench.trace import Event, Summary

SPEC = common.benchmark()
TRAIN, TRAIN4 = "train-qwen3-1.7b-7L-mean-m1", "train4-qwen3-0.6b-mean-m4"
NEW = {TRAIN: ["backward_ms.train", "forward_ms.train", "lm_head_ms.train",
               "optimizer_ms.train"],
       TRAIN4: ["agg_exposed_ms.train4", "agg_ms.train4", "backward_ms.train4",
                "forward_ms.train4", "lm_head_ms.train4", "optimizer_ms.train4"]}


@pytest.mark.parametrize("path, layer, head", [
    ("jit(step)/vmap(jvp(loss))/while/body/closed_call/dot_general", "forward", False),
    ("jit(step)/vmap(transpose(jvp(loss)))/while/body/checkpoint/"
     "rematted_computation/dot_general", "backward", False),
    ("jit(step)/vmap(jvp(loss))/lm_head/dot_general", "forward", True),
    ("jit(step)/vmap(transpose(jvp(loss)))/lm_head/jit(log_softmax)/exp",
     "backward", True),
    ("jit(step)/transpose(jvp(loss))/while/body/aggregate/all_to_all",
     "aggregate", False),
    ("jit(step)/aggregate/shard_map/psum", "aggregate", False),
    ("jit(step)/optimizer/sqrt", "optimizer", False),
    ("jit(step)/vmap(jvp(wloss))/mul", "other", False),
    ("params['embed']", "other", False),
    ("", "other", False),
], ids=["forward", "remat", "head", "head_backward", "agg_in_backward",
        "agg", "optimizer", "function_name", "argument", "none"])
def test_layer_of_a_path(path, layer, head):
    assert scopes.layer_of(path) == layer
    assert scopes.is_lm_head(path) is head


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/vmap(jvp(loss))/while/body/tanh" stack_frame_id=3}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(step)/vmap(jvp(loss))/while"}
  %fusion.2 = (f32[8]{0}, /*index=1*/f32[8,4]{1,0}) fusion(f32[8]{0} %y), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/vmap(transpose(jvp(loss)))/lm_head/dot_general"}
  %psum.1 = f32[8]{0} all-reduce(f32[8]{0} %g), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(step)/aggregate/shard_map/psum"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %psum.1), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/optimizer/sub"}
  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.3)
}
"""


def ev(name, start_ms, dur_ms):
    """A TPU-style event: the instruction's text, times in ms."""
    text = {"while.1": "(s32[], f32[8]{0:T(128)}) while((s32[], f32[8]) %t)",
            "fusion.1": "f32[8]{0:T(128)} fusion(f32[8] %x)",
            "fusion.2": "(f32[8]{0:T(128)}, f32[8,4]{1,0:T(8,128)}) fusion(f32[8] %y)",
            "psum.1": "f32[8]{0:T(128)} all-reduce(f32[8] %g)",
            "fusion.3": "f32[8]{0:T(128)} fusion(f32[8] %psum.1)",
            "copy.1": "f32[8]{0:T(128)} copy(f32[8] %fusion.3)"}.get(name, "f32[8] add()")
    return Event(f"%{name} = {text}", start_ms * 1e6, dur_ms * 1e6)


def chip(opt_start):
    """One chip's step: the forward scan (a while, with a body op inside
    it), the head's backward, the aggregation, the update from
    ``opt_start``, and a copy that no scope names."""
    return [ev("while.1", 0, 10), ev("fusion.1", 2, 3), ev("fusion.2", 10, 10),
            ev("psum.1", 20, 6), ev("fusion.3", opt_start, 6), ev("copy.1", 32, 1)]


@pytest.mark.parametrize("opt_start, exposed", [(24, 4.0), (26, 6.0)],
                         ids=["overlap", "no_overlap"])
def test_layer_times_of_hand_made_events(opt_start, exposed):
    module, op_map = scopes.parse_hlo(HLO)
    assert module == "jit_step"
    assert op_map["fusion.2"] == ("backward", True, (("f32", "8"), ("f32", "8,4")))
    s = Summary({"/device:TPU:0": chip(opt_start), "/device:TPU:1": chip(opt_start)},
                0.0, 1.0, [], [])
    got = scopes.split(s, 2, module, op_map)
    # the while and its body op are counted once; per step over 2 steps
    want = {"forward": 5.0, "backward": 5.0, "lm_head": 5.0, "aggregate": 3.0,
            "optimizer": 3.0, "other": 0.5, "agg_exposed": exposed / 2,
            "unjoined": 0.0, "join_miss": 0.0}
    assert {k: got[k] for k in want} == pytest.approx(want)
    assert got["busy"] == pytest.approx((31.0 if opt_start == 24 else 33.0) / 2)


def ctx_with(events, window):
    return {"config": {}, "traffic": {"window": "train"}, "window": window,
            "trace": Summary({"/device:TPU:0": events}, 0.0, 1.0, [], [])}


@pytest.mark.parametrize("extra, shape, ok", [
    ([], None, True),
    ([ev("fusion.9", 40, 0.3)], None, True),        # under 1% of busy time
    ([ev("fusion.9", 40, 1.0)], None, False),       # an op the map lacks
    ([], "f32[9]{0} fusion(f32[8] %x)", False),     # a stale map: wrong shape
], ids=["joined", "small_miss", "missing_op", "wrong_shape"])
def test_reader_reads_nothing_when_the_join_fails(monkeypatch, extra, shape, ok):
    monkeypatch.setattr(scopes, "compiled_step_text", lambda cfg, tr: HLO)
    evs = chip(26) + extra
    if shape:
        evs = [e._replace(name=f"%fusion.3 = {shape}") if e.short == "fusion.3"
               else e for e in evs]
    got = scopes.read(ctx_with(evs, {"steps": 1}), "optimizer")
    assert (got == pytest.approx(6.0)) if ok else (got is None)


def test_reader_reads_nothing_of_a_program_without_scopes(monkeypatch):
    """A step compiled without the scopes (an older program) joins, but
    names no layer: its metrics are left out, not read as nought."""
    bare = "\n".join(ln.split(", metadata=")[0] for ln in HLO.splitlines())
    monkeypatch.setattr(scopes, "compiled_step_text", lambda cfg, tr: bare)
    ctx = ctx_with(chip(26), {"steps": 1})
    assert scopes.layers(ctx)["join_miss"] == 0.0
    for name in NEW[TRAIN] + NEW[TRAIN4]:
        assert common.layer_metric(name).read(ctx) is None


def test_reader_reads_nothing_on_an_agg_cell(monkeypatch):
    def refuse(cfg, tr):
        raise AssertionError("an agg cell has no step to compile")
    monkeypatch.setattr(scopes, "compiled_step_text", refuse)
    ctx = ctx_with(chip(26), {"rounds": 10, "m": 8, "d": 64})
    for name in NEW[TRAIN] + NEW[TRAIN4]:
        assert common.layer_metric(name).read(ctx) is None


def test_traced_tiny_train_cell_reads_the_layers(cpu_extra):
    cell = common.cell(TRAIN, SPEC)
    out = run.run_cell(SPEC, cell, tiny_config(cell["config"]),
                       tiny_traffic(cell["traffic"]), 2**31 + 23, 0.5, True,
                       jax.devices(), cpu_extra)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items() if k in NEW[TRAIN]}
    assert sorted(got) == NEW[TRAIN] and all(v > 0 for v in got.values())
    assert got["lm_head_ms.train"] < got["forward_ms.train"] + got["backward_ms.train"]


SCRIPT = r"""
import json, sys
import jax
from bench import common, run
from bench.tests.conftest import tiny_config, tiny_traffic
spec = common.benchmark()
cell = common.cell(sys.argv[1], spec)
extra = {"trace_plane": "/host:CPU", "trace_line": None,
         "peak": common.peaks("TPU v5 lite")}
out = run.run_cell(spec, cell, tiny_config(cell["config"]),
                   tiny_traffic(cell["traffic"]), 2**31 + 29, 0.5, True,
                   jax.devices(), extra)
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "metrics": {k: v["value"] for k, v in out["metrics"].items()}}))
"""


def test_traced_tiny_four_worker_cell_reads_the_layers():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(common.ROOT), str(common.ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, TRAIN4], env=env,
                          cwd=common.ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    got = {k: v for k, v in out["metrics"].items() if k in NEW[TRAIN4]}
    assert sorted(got) == NEW[TRAIN4] and all(v >= 0 for v in got.values())
    assert 0 < got["agg_exposed_ms.train4"] <= got["agg_ms.train4"]


def test_stale_paths_in_the_compile_cache_are_not_read(tmp_path, monkeypatch):
    """The persistent cache already holds the same step built without the
    scopes (by default its key leaves metadata out): the reader's compile
    still gives the scopes' paths."""
    import contextlib

    from jax.experimental.compilation_cache import compilation_cache as cc
    cell = common.cell(TRAIN, SPEC)
    cfg, tr = tiny_config(cell["config"]), tiny_traffic(cell["traffic"])
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        with monkeypatch.context() as mp:
            mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            bare = scopes.parse_hlo(scopes.compiled_step_text(cfg, tr))[1]
        assert {v[0] for v in bare.values()} == {"other"}
        assert any(tmp_path.iterdir())
        got = scopes.parse_hlo(scopes.compiled_step_text(cfg, tr))[1]
        assert {"forward", "backward", "optimizer", "aggregate"} <= {v[0] for v in got.values()}
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
