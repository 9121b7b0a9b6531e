"""Operation and byte counts of the benchmark against counts worked out
by hand, and the table of peaks."""
import pytest

from bench import common, counts

# qwen3-0.6b: embedding 151,936 x 1,024 = 155,582,464; per layer
# q 1024x2048 + k,v 1024x1024 x2 + o 2048x1024 = 6,291,456 and MLP
# 3 x 1024 x 3072 = 9,437,184, norms 2x1024 + 2x128 = 2,304; 28 layers;
# final norm 1,024.
N_06B = 155_582_464 + 28 * (6_291_456 + 9_437_184 + 2_304) + 1_024
# qwen3-1.7b-7L: embedding 151,936 x 2,048 = 311,164,928; per layer
# 2x(2048x2048) + 2x(2048x1024) = 12,582,912, MLP 3x2048x6144 =
# 37,748,736, norms 2x2048 + 2x128 = 4,352; 7 layers; final norm 2,048.
N_17B_7L = 311_164_928 + 7 * (12_582_912 + 37_748_736 + 4_352) + 2_048


def test_param_counts_by_hand():
    assert N_06B == 596_049_920
    assert N_17B_7L == 663_518_976
    assert counts.n_params(common.config("qwen3-0.6b")) == N_06B
    assert counts.n_params(common.config("qwen3-1.7b-7L")) == N_17B_7L
    assert common.config("qwen3-0.6b")["n_params"] == N_06B
    assert common.config("qwen3-1.7b-7L")["n_params"] == N_17B_7L


def test_param_counts_match_program():
    from bench.windows import train
    from repro.models import params as PM
    from repro.models import transformer as TF
    for name in ("qwen3-0.6b", "qwen3-1.7b-7L"):
        cfg = common.config(name)
        assert PM.count_params(TF.param_defs(train.model_config(cfg))) == \
            counts.n_params(cfg)


def test_train_flops_per_token_by_hand():
    cfg = common.config("qwen3-1.7b-7L")
    matmul = 7 * (12_582_912 + 37_748_736) + 311_164_928
    assert counts.matmul_params(cfg) == matmul
    # causal scores and values: 7 layers x 2 products x 2 FLOPs x
    # 16 heads x 128 x (1024 + 1) / 2 keys on average
    attn = 7 * 2 * 2 * 16 * 128 * 512.5
    assert counts.attention_fwd_flops_per_token(cfg, 1024) == attn
    assert counts.train_flops_per_token(cfg, 1024) == 6 * matmul + 3 * attn


def test_kernel_bytes_at_full_size_by_hand():
    m, d = 8, 596_049_920
    G = 8 * 596_049_920 * 2                       # bf16 worker matrix
    assert G == 9_536_798_720
    assert counts.fused_stats_bytes(m, d, "bfloat16") == G + 64
    assert counts.select_mean_bytes(m, d, "bfloat16") == \
        G + 64 + 8 + 596_049_920 * 4 + 32
    assert counts.agg_round_bytes(m, d, "bfloat16") == 11_920_998_400
    # the sorting network on 8 rows: 24 compare-exchanges of 2 ops
    assert counts.fused_stats_flops(m, d) == d * (5 * 8 + 48 + 3 * 8)
    assert counts.select_mean_flops(m, d) == 2 * 8 * d


def test_roofline_share_takes_the_longer_bound():
    peak = common.peaks("TPU v5 lite")
    share, bound = counts.roofline_share(0.0, 819e9, 2.0, peak)
    assert (share, bound) == (50.0, "memory")
    share, bound = counts.roofline_share(197e12, 1.0, 4.0, peak)
    assert (share, bound) == (25.0, "compute")


def test_peaks_unknown_device_is_an_error():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")


def test_layer_bucket_columns_by_hand():
    """One qwen3-0.6b decoder layer, norms included: the blocked scope's
    bucket."""
    from bench.windows import agg
    d = agg.columns(common.config("qwen3-0.6b"), common.traffic("agg-brsgd-m8-bucket"))
    assert d == 6_291_456 + 9_437_184 + 2_304 == 15_730_944
    assert d % agg.TILE            # not whole tiles: the pad copy is made
