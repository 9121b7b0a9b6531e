"""Operations and bytes of the measured work, computed from shapes.

Model configurations are the JSON files of ``configs/`` (Hugging Face
key names).  Every count here is the least work the algorithm needs,
whatever implements it, so a later change that fuses or removes a
kernel is still bounded by it.
"""
from __future__ import annotations

import math

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
            "float8_e4m3fn": 1}


def dims(cfg: dict) -> dict:
    """The sizes the counts use, from a configuration file."""
    return {
        "V": cfg["vocab_size"], "D": cfg["hidden_size"],
        "F": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
        "H": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
        "Dh": cfg["head_dim"], "tied": cfg["tie_word_embeddings"],
    }


def layer_matmul_params(cfg: dict) -> int:
    d = dims(cfg)
    attn = d["D"] * d["H"] * d["Dh"] * 2 + d["D"] * d["K"] * d["Dh"] * 2
    mlp = 3 * d["D"] * d["F"]
    return attn + mlp


def layer_norm_params(cfg: dict) -> int:
    d = dims(cfg)
    return 2 * d["D"] + 2 * d["Dh"]          # ln1, ln2, q_norm, k_norm


def n_params(cfg: dict) -> int:
    """Every parameter: embedding (and head unless tied), the layers,
    the final norm."""
    d = dims(cfg)
    emb = d["V"] * d["D"] * (1 if d["tied"] else 2)
    return (emb + d["L"] * (layer_matmul_params(cfg) + layer_norm_params(cfg))
            + d["D"])


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product: the layers'
    projections and the LM head (the tied embedding counted once, as
    the head; the embedding lookup is a gather)."""
    d = dims(cfg)
    return d["L"] * layer_matmul_params(cfg) + d["V"] * d["D"]


def attention_fwd_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal score (QKᵀ) and value (PV) products of one token's forward
    pass, averaged over the positions of a sequence of ``seq`` tokens:
    position t attends to t + 1 keys."""
    d = dims(cfg)
    mean_ctx = (seq + 1) / 2
    return d["L"] * 2 * 2 * d["H"] * d["Dh"] * mean_ctx


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token: forward and backward (3× the
    forward) of every matrix product.  Recomputation does not count."""
    return 6 * matmul_params(cfg) + 3 * attention_fwd_flops_per_token(cfg, seq)


# ---- the aggregation round over G [m, d] ---------------------------------

def agg_round_bytes(m: int, d: int, dtype: str) -> int:
    """Least HBM traffic of one robust aggregation: read G once, write
    the float32 aggregate once."""
    return m * d * ITEMSIZE[dtype] + d * 4


def fused_stats_bytes(m: int, d: int, dtype: str) -> int:
    """The statistics pass: reads G, writes the (m, 1) int32 scores and
    float32 l1 partials."""
    return m * d * ITEMSIZE[dtype] + 2 * m * 4


def fused_stats_flops(m: int, d: int) -> float:
    """Elementwise operations of the statistics pass per column: the
    column sum and the comparisons and counts of the majority score
    (about 5m), the median's sorting network on m rows padded to a power
    of two p (p·log2(p)·(log2(p)+1)/2 min/max pairs) and the l1 sum
    (3m)."""
    p = 1 << max(1, math.ceil(math.log2(m)))
    lg = int(math.log2(p))
    sort_ops = p * lg * (lg + 1) // 2
    return float(d) * (5 * m + sort_ops + 3 * m)


def select_mean_bytes(m: int, d: int, dtype: str) -> int:
    """The selection and combine pass: reads G and the (m, 2) scores and
    l1 and two thresholds, writes the float32 [d] sum and the (m, 1)
    weights."""
    return m * d * ITEMSIZE[dtype] + m * 2 * 4 + 2 * 4 + d * 4 + m * 4


def select_mean_flops(m: int, d: int) -> float:
    """One multiply and one add per element of G."""
    return 2.0 * m * d


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share of the roofline in %, the bound that sets it) for work of
    ``flops`` and ``nbytes`` that took ``seconds``."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
