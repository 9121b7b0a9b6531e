"""The program's named scopes reach the compiled training step, where the
benchmark's reader (``bench/scopes.py``) maps each instruction to a
layer; and the training launcher's host spans reach a profiler trace."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO, run_multidevice

sys.path.insert(0, REPO)
from bench import scopes, trace  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))


def scope_summary(mesh_shape, scope: str, aggregator: str) -> dict:
    """What the reader's mapping rule finds in the compiled step of a
    reduced qwen3 on a (data, model) mesh of ``mesh_shape``."""
    from repro.configs import ByzantineConfig, TrainConfig, get_config
    from repro.launch.mesh import make_mesh
    from repro.models import params as PM
    from repro.models import transformer as TF
    from repro.training.step import build_train_step

    cfg = get_config("qwen3-0.6b").reduced()
    tcfg = TrainConfig(model=cfg, byzantine=ByzantineConfig(aggregator=aggregator),
                       optimizer="adamw", agg_scope=scope)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    bundle = build_train_step(tcfg, mesh)
    psh, osh, bsh = bundle.shardings(mesh)
    key = jax.random.PRNGKey(0)
    defs = TF.param_defs(cfg)
    shapes = jax.eval_shape(lambda k: PM.init_params(defs, k), key)
    with_sh = lambda t, sh: jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=h), t, sh)
    params = with_sh(shapes, psh)
    opt = {"m": with_sh(shapes, osh["m"]), "v": with_sh(shapes, osh["v"])}
    m = mesh.devices.size if scope == "blocked" else mesh_shape[0]
    batch = {"tokens": jax.ShapeDtypeStruct((m, 2, 16), jnp.int32,
                                            sharding=bsh["tokens"])}
    text = bundle.step_fn.lower(params, opt, batch,
                                jax.ShapeDtypeStruct((), jnp.int32),
                                jax.eval_shape(lambda: key)).compile().as_text()
    paths = [p for p, _rhs in scopes.instructions(text).values()]
    layers = [scopes.layer_of(p) for p in paths]
    return {
        "layers": sorted(set(layers)),
        "lm_head": sorted({l for p, l in zip(paths, layers) if scopes.is_lm_head(p)}),
        "optimizer_transposed": sum(l == "optimizer" and "transpose(" in p
                                    for p, l in zip(paths, layers)),
        "agg_under_backward": sorted({
            l for p, l in zip(paths, layers)
            if "transpose(" in p and {"loss", "aggregate"} <= scopes.scope_names(p)}),
    }


def check(got: dict, scope: str):
    assert got["layers"] == ["aggregate", "backward", "forward", "optimizer", "other"]
    assert got["lm_head"] == ["backward", "forward"]
    assert got["optimizer_transposed"] == 0
    # the blocked scope aggregates each bucket inside the backward scan:
    # precedence sends those instructions to the aggregation
    assert got["agg_under_backward"] == (["aggregate"] if scope == "blocked" else [])


@pytest.mark.parametrize("scope", ["global", "blocked"])
def test_scopes_map_one_worker_step(scope):
    check(scope_summary((1, 1), scope, "mean"), scope)


def test_scopes_map_four_worker_steps():
    out = run_multidevice(f"""
import json, sys
sys.path[:0] = [{TESTS!r}, {REPO!r}]
from test_tracing import scope_summary
print(json.dumps({{s: scope_summary((4, 1), s, "brsgd") for s in ("global", "blocked")}}))
""", n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    for scope, summary in got.items():
        check(summary, scope)


SPANS = ("train", "feed", "dispatch", "supervise", "log_sync", "telemetry",
         "checkpoint")


@pytest.mark.parametrize("supervise", [False, True], ids=["plain", "supervise"])
def test_train_main_host_spans(tmp_path, capsys, monkeypatch, supervise):
    from repro.launch import compile_cache, train
    # keep this test process's compiles out of the checkout's cache
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    steps = 3
    argv = ["--arch", "qwen3-0.6b", "--reduced", "--steps", str(steps),
            "--mesh", "1x1", "--seq", "16", "--aggregator", "mean",
            "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "1"]
    with jax.profiler.trace(str(tmp_path / "trace")):
        train.main(argv + (["--supervise"] if supervise else []))
    spans = trace.host_spans(trace.load(tmp_path / "trace"), SPANS)
    steps_ = [s for s in spans if s.name == "train"]
    assert len(steps_) == steps
    run = "supervise" if supervise else "dispatch"
    for st in steps_:
        inner = [s.name for s in spans if s.name != "train"
                 and st.start_ns <= s.start_ns and s.end_ns <= st.end_ns]
        assert inner == ["feed", run, "log_sync", "telemetry", "checkpoint"]
    done = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("done:")]
    assert len(done) == 1 and f"steps 1..{steps - 1}:" in done[0]
