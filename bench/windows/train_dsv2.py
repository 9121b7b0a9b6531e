"""Training window of DeepSeek-V2 as one chip's share (MLA, and an MoE
layer that holds some of the routed experts): the train window of
``windows/train.py`` with this model's ModelConfig and plain reference
(``reference/deepseek_v2.py``).

The step, its timing loop, the token stream, the readings compared with
the reference and the comparison are the train window's own: this file
loads ``windows/train.py`` as a module of its own and gives that module
this model's ``model_config`` and reference.  What it adds is the
routing counter: every step's ``moe_pairs`` (the (token, expert) pairs
the held experts computed, summed over layers) stays on the device
while the window runs, and is summed after it into ``moe_pairs`` per
step in the result.
"""
from __future__ import annotations

import numpy as np

from bench import common
from bench.reference import deepseek_v2 as ref

T = common.window("train")      # a module instance of its own
SPANS = T.SPANS
compare = T.compare
token_batch = T.token_batch
check = T.check


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the router
    scores ``n_routed_experts × expert_parallel`` experts, and this chip
    holds the first ``n_routed_experts`` of them."""
    common.program_on_path()
    from repro.configs.base import AttentionSpec, ModelConfig, MoESpec, YarnSpec
    if cfg.get("q_lora_rank") or not cfg["seq_aux"] or cfg["topk_method"] != "greedy":
        raise ValueError("the program routes by greedy top-k with the "
                         "sequence-wise loss, with MLA of a full-rank query")
    rs = cfg["rope_scaling"]
    yarn = None if rs is None else YarnSpec(
        factor=float(rs["factor"]),
        original_max_position=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        attention=AttentionSpec(
            kind="mla", n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=dn + dr,
            kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=dn, qk_rope_dim=dr,
            v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
            rope_scaling=yarn, rope_interleaved=True),
        activation=cfg["hidden_act"],
        moe=MoESpec(
            n_experts=cfg["n_routed_experts"] * cfg["expert_parallel"],
            top_k=cfg["num_experts_per_tok"], n_shared=cfg["n_shared_experts"],
            d_ff_expert=cfg["moe_intermediate_size"],
            norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            balance="seq", aux_loss=cfg["aux_loss_alpha"],
            expert_first=0, n_held=cfg["n_routed_experts"]),
        n_dense_layers=cfg["first_k_dense_replace"],
        tie_embeddings=cfg["tie_word_embeddings"], rms_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], source=cfg["source"])


# the train window builds its step and reference through these two names
T.model_config = model_config
T.ref = ref


class Run(T.Run):
    """The train window's compiled step and state, which keeps each
    step's routing counter on the device."""

    def __init__(self, ctx: dict):
        super().__init__(ctx)
        self.pairs = []

    def run_step(self, batch):
        met = super().run_step(batch)
        self.pairs.append(met["moe_pairs"])
        return met


def setup(ctx: dict) -> dict:
    run = Run(ctx)
    run.start(ctx["seed"])
    readings = run.first_steps()
    return {"run": run, "readings": readings}


def measure(state: dict, seconds: float) -> dict:
    """The train window's measure, then the mean of the timed steps'
    ``moe_pairs``, read back after the window."""
    import jax
    run = state["run"]
    run.pairs = []
    out = T.measure(state, seconds)
    out["moe_pairs"] = float(np.sum(jax.device_get(run.pairs))) / max(out["steps"], 1)
    run.pairs = []
    return out
