"""DeepSeek-V2-Lite's chip share: its plain reference against the
program, its counts worked out by hand, and its cell traced at a tiny
size on the CPU."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, counts_dsv2, run, scopes_dsv2
from bench.reference import deepseek_v2 as ref
from bench.trace import Event, Summary
from bench.windows import train_dsv2 as W

CELL = "train-deepseek-v2-lite-6L-e8-mean-m1"
CONFIG = "deepseek-v2-lite-6L-e8"
# widths a test can hold; 3 layers (one dense), 4 experts held of 8, top-3.
# The YaRN ramp is set to the tiny lengths: it stretches the low
# frequencies from position 8 on.
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
            expert_parallel=2, num_experts_per_tok=3)
NEW = ["experts_roofline.dsv2", "mfu.dsv2", "mla_ms.dsv2", "moe_dispatch_ms.dsv2",
       "moe_ms.dsv2"]
TRAIN = ["backward_ms.train", "forward_ms.train", "idle_share.train",
         "lm_head_ms.train", "optimizer_ms.train"]


def tiny_config(**kw) -> dict:
    cfg = copy.deepcopy(common.config(CONFIG))
    cfg.update(TINY, **kw)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], original_max_position_embeddings=8)
    return cfg


def tiny_traffic() -> dict:
    return dict(common.traffic("train-mla-moe-m1-s4096"), seq=32, batch_per_worker=2,
                reference_rows=1)


def program_loss_and_grad(cfg, params, toks):
    from repro.models import transformer as TF
    mcfg = W.model_config(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: TF.loss_fn(mcfg, p, {"tokens": jnp.asarray(toks)}),
            has_aux=True)(params)


def test_loss_and_gradient_match_the_program():
    cfg = tiny_config()
    params = ref.init(cfg, jax.random.PRNGKey(0))
    toks = W.token_batch(cfg["vocab_size"], 5, 0, (2, 24))
    (want, met), g_want = program_loss_and_grad(cfg, params, toks)
    got, g_got = ref.loss_and_grad(cfg, params, toks)
    # both float32 at the highest precision over the same operations in
    # another order: the loss to float32 rounding of a sum of ~50 terms
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(met["aux"]) > 0 and float(met["moe_pairs"]) > 0
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        # every leaf to 1e-5 of its largest entry: the rounding of a few
        # hundred float32 terms per entry, far below what a changed
        # operation moves (the cases below)
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("fault", [
    {"norm_topk_prob": True}, {"num_experts_per_tok": 2}, {"rope_scaling": None}],
    ids=["renormalised", "top_k_less_one", "plain_rope"])
def test_each_planted_fault_moves_the_gradient(fault):
    """The faults that calibration plants in the reference each move the
    gradient far beyond the agreement above."""
    cfg = tiny_config()
    params = ref.init(cfg, jax.random.PRNGKey(0))
    toks = W.token_batch(cfg["vocab_size"], 5, 0, (2, 24))
    _, g_want = program_loss_and_grad(cfg, params, toks)
    _, g_bad = ref.loss_and_grad(dict(cfg, **fault), params, toks)
    gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(jax.tree.leaves(g_bad), jax.tree.leaves(g_want))]
    assert max(gaps) > 1e-2


def test_reference_layout_is_the_programs():
    from repro.models import params as PM
    from repro.models import transformer as TF
    for cfg in (common.config(CONFIG), tiny_config()):
        have = jax.tree.map(lambda d: tuple(d.shape), TF.param_defs(W.model_config(cfg)),
                            is_leaf=PM.is_param_def)
        assert have == ref.shapes(cfg)


# embedding and head 2 x 12,800 x 2,048; MLA per layer: q 2048x16x192,
# latent 2048x512, rope key 2048x64, key and value up 2 x 512x16x128,
# output 16x128x2048, kv_norm 512; dense FFN 3 x 2048 x 10,944; MoE:
# router 2048 x 64, 8 experts of 3 x 2048 x 1408, shared 3 x 2048 x 2816;
# two norms of 2,048 a layer; final norm 2,048.
MLA = 6_291_456 + 1_048_576 + 131_072 + 2 * 1_048_576 + 4_194_304 + 512
DENSE = MLA + 67_239_936 + 4_096
MOE = MLA + 131_072 + 69_206_016 + 17_301_504 + 4_096
N = 52_428_800 + DENSE + 5 * MOE + 2_048


def test_param_count_by_hand():
    assert (MLA, DENSE, MOE) == (13_763_072, 81_007_104, 100_405_760)
    assert N == 635_466_752
    cfg = common.config(CONFIG)
    assert counts_dsv2.n_params(cfg) == N == cfg["n_params"]
    from repro.models import params as PM
    from repro.models import transformer as TF
    assert PM.count_params(TF.param_defs(W.model_config(cfg))) == N


def test_train_flops_per_step_by_hand():
    """8.81 TFLOP a step of 4,096 tokens at the expected load: each token
    sends 6 x 8 / 64 = 0.75 pairs per layer to the held experts."""
    cfg = common.config(CONFIG)
    dense = 6 * (MLA - 512) + 67_239_936 + 5 * (131_072 + 17_301_504) + 26_214_400
    assert counts_dsv2.dense_matmul_params(cfg) == dense
    # scores over 128 + 64 and values over 128 dimensions, 16 heads
    attn = 6 * 2 * 16 * (192 + 128) * 2048.5
    assert counts_dsv2.attention_fwd_flops_per_token(cfg, 4096) == attn
    per_token = counts_dsv2.train_flops_per_token(cfg, 4096, 5 * 0.75)
    assert per_token == 6 * (dense + 5 * 0.75 * 3 * 2048 * 1408) + 3 * attn
    assert per_token * 4096 == pytest.approx(8.81e12, rel=1e-3)
    pairs = 5 * 4096 * 0.75
    assert counts_dsv2.experts_flops(cfg, pairs) == 18 * pairs * 2048 * 1408
    assert counts_dsv2.experts_bytes(cfg, pairs) == \
        3 * 5 * 8 * 3 * 2048 * 1408 * 4 + 4 * pairs * 2048 * 4


HLO = """HloModule jit_step

ENTRY %main (x: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp(loss)/while/body/mla/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/transpose(jvp(loss))/while/body/moe/experts/ragged_dot_general"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/jvp(loss)/while/body/moe/dispatch/sort"}
  %ragged-dot-none.1 = f32[8]{0} custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.4 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/optimizer/sub"}
}
"""


def test_scope_times_of_hand_made_events(monkeypatch):
    monkeypatch.setattr(scopes_dsv2.scopes, "compiled_step_text", lambda c, t: HLO)
    evs = [Event("%fusion.1 = f32[8]{0} fusion()", 0, 4e6),
           Event("%fusion.2 = f32[8]{0} fusion()", 4e6, 3e6),
           Event("%fusion.3 = f32[8]{0} fusion()", 7e6, 1e6),
           Event("%fusion.4 = f32[8]{0} fusion()", 8e6, 2e6),
           Event("%ragged-dot-none.1 = f32[8]{0} custom-call()", 10e6, 2e6)]

    def ctx(events):
        return {"config": {}, "traffic": {}, "window": {"steps": 2},
                "trace": Summary({"/device:TPU:0": events}, 0.0, 1.0, [], [])}

    c = ctx(evs)
    got = {k: scopes_dsv2.read(c, k) for k in scopes_dsv2.SCOPES}
    # the compiler's grouped-product kernel counts under experts and moe
    assert got == {"mla": 2.0, "moe": 3.0, "experts": 2.5, "dispatch": 0.5}
    # 1 ms of 13 that no instruction of the map names: nothing is read
    stale = ctx(evs + [Event("%fusion.9 = f32[8]{0} fusion()", 12e6, 1e6)])
    assert scopes_dsv2.read(stale, "mla") is None


def test_traced_tiny_cell_reads_its_metrics(cpu_extra):
    spec = common.benchmark()
    cell = common.cell(CELL, spec)
    out = run.run_cell(spec, cell, tiny_config(), tiny_traffic(), 2**31 + 41, 0.5,
                       True, jax.devices(), cpu_extra)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert sorted(got) == sorted(NEW + TRAIN)
    assert all(v > 0 for k, v in got.items() if k != "idle_share.train")
    assert got["moe_dispatch_ms.dsv2"] < got["moe_ms.dsv2"]
    assert got["mla_ms.dsv2"] + got["moe_ms.dsv2"] < \
        got["forward_ms.train"] + got["backward_ms.train"]
