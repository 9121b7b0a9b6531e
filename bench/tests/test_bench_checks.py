"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and for the control of a lower
precision; the harness's look for a chip is skipped and the rest of a run
is driven on the CPU at a small size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, run
from bench.tests import faults
from bench.tests.conftest import tiny_config, tiny_traffic

SPEC = common.benchmark()
AGG = "agg-qwen3-0.6b-bucket-brsgd-m8"
TRAIN = "train-qwen3-1.7b-7L-mean-m1"


def run_tiny(cell_name, extra=None, seed=2**31 + 11):
    cell = common.cell(cell_name, SPEC)
    return run.run_cell(SPEC, cell, tiny_config(cell["config"]),
                        tiny_traffic(cell["traffic"]), seed, 0.2, False,
                        jax.devices(), extra)


def test_sound_runs_are_correct():
    for name in (AGG, TRAIN):
        out = run_tiny(name)
        assert out["correct"] is True, out["checks"]
        assert out["failed"] == 0 and out["attempted"] > 0


def _answer_altered(real):
    def broken(G, cfg, **kw):
        agg, st = real(G, cfg, **kw)
        return agg.at[G.shape[1] // 3].add(1.0 / 32), st
    return broken


def _half_rows(real):
    def broken(G, cfg, **kw):
        m = G.shape[0]
        agg, st = real(G[m // 2:], cfg, **kw)
        sel = jnp.concatenate([jnp.zeros((m // 2,), bool), st.selected])
        return agg, st._replace(selected=sel)
    return broken


@pytest.mark.parametrize("fault", [_answer_altered, _half_rows],
                         ids=["answer_altered", "half_of_the_rows"])
def test_agg_faults_are_caught(monkeypatch, fault):
    from repro.core import engine
    monkeypatch.setattr(engine, "aggregate_local", fault(engine.aggregate_local))
    out = run_tiny(AGG)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_agg_control_fails():
    from bench.windows import agg
    cell = common.cell(AGG, SPEC)
    tr = tiny_traffic(cell["traffic"])
    state = agg.setup({"config": tiny_config(cell["config"]), "traffic": tr,
                       "seed": 5, "devices": jax.devices()})
    kept = agg.reference_kept(state)
    kept_q = agg.reference_kept(state, quant=jnp.float8_e4m3fn)
    gap = agg.ref.control_gap(state["G"], kept, kept_q, jnp.float8_e4m3fn)
    assert gap > tr["limits"]["aggregate_max_gap"]


@pytest.mark.parametrize("fault", sorted(faults.TRAIN_FAULTS))
def test_train_faults_are_caught(monkeypatch, fault):
    faults.TRAIN_FAULTS[fault](monkeypatch.setattr)
    out = run_tiny(TRAIN)
    assert out["correct"] is False, out["checks"]


def test_train_control_fails():
    """The reference in bfloat16, put in the program's place, fails one of
    the cell's numbers."""
    from bench.windows import train
    cell = common.cell(TRAIN, SPEC)
    tr = tiny_traffic(cell["traffic"])
    r = train.Run({"config": tiny_config(cell["config"]), "traffic": tr})
    r.start(7)
    want = r.reference()
    low = r.reference(precision=jax.lax.Precision.DEFAULT, dtype=jnp.bfloat16)
    numbers = train.compare(low, want)
    assert any(v > tr["limits"][k] for k, v in numbers.items()
               if k in tr["limits"]), numbers
    assert np.isfinite(list(numbers.values())).all()
