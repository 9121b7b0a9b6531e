"""Operations and bytes of DeepSeek-V2's training step (MLA and the MoE
layer of the held experts), computed from a configuration file's shapes
and from the routed pairs that the step counted.  As in ``counts.py``,
every count is the least work the algorithm needs, whatever implements
it.  ``pairs`` is the (token, expert) pairs that held experts computed
in one step, summed over layers (the program's ``moe_pairs``)."""
from __future__ import annotations

from bench.counts import ITEMSIZE


def dims(cfg: dict) -> dict:
    n_dense = cfg["first_k_dense_replace"]
    return {
        "V": cfg["vocab_size"], "D": cfg["hidden_size"],
        "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
        "L": cfg["num_hidden_layers"], "L_dense": n_dense,
        "L_moe": cfg["num_hidden_layers"] - n_dense,
        "H": cfg["num_attention_heads"], "R": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "E_held": cfg["n_routed_experts"],
        "E": cfg["n_routed_experts"] * cfg["expert_parallel"],
        "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
    }


def mla_matmul_params(cfg: dict) -> int:
    """One layer's attention projections: q, the latent and rope-key
    down-projections, the key and value up-projections, the output."""
    d = dims(cfg)
    return (d["D"] * d["H"] * (d["dn"] + d["dr"]) + d["D"] * d["R"] + d["D"] * d["dr"]
            + d["R"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["D"])


def expert_params(cfg: dict) -> int:
    """One routed expert's gated MLP."""
    d = dims(cfg)
    return 3 * d["D"] * d["Fe"]


def n_params(cfg: dict) -> int:
    """Every parameter: embedding and head, the layers (norms included),
    the final norm."""
    d = dims(cfg)
    mla = mla_matmul_params(cfg) + d["R"]                  # kv_norm
    dense = mla + 3 * d["D"] * d["F"] + 2 * d["D"]
    moe = (mla + d["D"] * d["E"] + d["E_held"] * expert_params(cfg)
           + 3 * d["D"] * d["Fs"] + 2 * d["D"])
    return 2 * d["V"] * d["D"] + d["L_dense"] * dense + d["L_moe"] * moe + d["D"]


def dense_matmul_params(cfg: dict) -> int:
    """Parameters in a product with every token: the layers' attention,
    the dense FFNs, the routers at their full width, the shared experts
    and the head (the embedding lookup is a gather)."""
    d = dims(cfg)
    return (d["L"] * mla_matmul_params(cfg) + d["L_dense"] * 3 * d["D"] * d["F"]
            + d["L_moe"] * (d["D"] * d["E"] + 3 * d["D"] * d["Fs"]) + d["D"] * d["V"])


def attention_fwd_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal score (q·k over nope + rope dimensions) and value products
    of one token's forward pass, averaged over the positions of a
    sequence of ``seq`` tokens."""
    d = dims(cfg)
    mean_ctx = (seq + 1) / 2
    return d["L"] * 2 * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * mean_ctx


def train_flops_per_token(cfg: dict, seq: int, pairs_per_token: float) -> float:
    """Model FLOPs of one trained token: forward and backward (3× the
    forward) of every matrix product, the held experts' counted by the
    pairs they computed.  Recomputation does not count."""
    active = dense_matmul_params(cfg) + pairs_per_token * expert_params(cfg)
    return 6 * active + 3 * attention_fwd_flops_per_token(cfg, seq)


def experts_flops(cfg: dict, pairs: float) -> float:
    """The grouped products of the held experts over ``pairs`` pairs:
    three of 2·D·Fe per pair forward, twice that backward."""
    return 3 * 2 * pairs * expert_params(cfg)


def experts_bytes(cfg: dict, pairs: float) -> float:
    """Least HBM traffic of the grouped products, forward and backward:
    every held expert's weights read forward, read backward and their
    gradient written, and each pair's input and output rows read or
    written once each way."""
    d = dims(cfg)
    item = ITEMSIZE[cfg["torch_dtype"]]
    weights = 3 * d["L_moe"] * d["E_held"] * expert_params(cfg) * item
    rows = 4 * pairs * d["D"] * item
    return weights + rows
