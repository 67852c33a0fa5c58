"""The FLOP and byte counts against hand counts at one tiny shape, and the
table of peaks."""
import pytest

from benchmark import flops, spec

BENCH = spec.Bench()
MISTRAL = BENCH.family({"model_type": "mistral"})
PHI = BENCH.family({"model_type": "phi"})
ARCH = {"hidden_size": 8, "intermediate_size": 16, "num_layers": 2,
        "num_heads": 2, "num_kv_heads": 1, "head_dim": 4, "vocab_size": 10,
        "sliding_window": None}


def test_causal_pairs():
    assert flops.causal_pairs(4) == 1 + 2 + 3 + 4
    assert flops.causal_pairs(4, window=2) == 1 + 2 + 2 + 2
    assert flops.causal_pairs(4, window=9) == 10


def test_matmul_params_by_hand():
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8          # wq, wk + wv, wo
    mlp = 3 * 8 * 16
    assert flops.attention_params(ARCH) == attn
    assert MISTRAL.matmul_params(ARCH) == 2 * (attn + mlp) + 8 * 10
    # phi's MLP has two matrices
    assert PHI.matmul_params(ARCH) == 2 * (attn + 2 * 8 * 16) + 80


def test_train_flops_per_token_by_hand():
    seq = 4
    n = MISTRAL.matmul_params(ARCH)
    # attention forward: q k^T and p v, 2 * head_dim FLOPs each, per pair
    # and head; backward twice that; two layers; 10 pairs over 4 tokens
    attn = 3 * (2 * 2 * 4) * 2 * 2 * 10 / seq
    assert flops.attention_train_flops(ARCH, seq) == attn
    assert MISTRAL.train_flops_per_token(ARCH, seq) == 6 * n + attn
    assert PHI.train_flops_per_token(ARCH, seq) \
        == 6 * PHI.matmul_params(ARCH) + attn


def test_flash_kernel_counts_by_hand():
    pairs = flops.causal_pairs(4)
    per_product = 2 * 4 * 3 * 2 * pairs        # 2 d, batch 3, heads 2
    assert flops.flash_flops("fwd", 3, 2, 4, 4) == 2 * per_product
    assert flops.flash_flops("dq", 3, 2, 4, 4) == 3 * per_product
    assert flops.flash_flops("dkv", 3, 2, 4, 4) == 4 * per_product
    q, kv, row = 3 * 2 * 4 * 4 * 2, 3 * 1 * 4 * 4 * 2, 3 * 2 * 4 * 4
    assert flops.flash_bytes("fwd", 3, 2, 1, 4, 4) == 2 * q + 2 * kv + row
    assert flops.flash_bytes("dq", 3, 2, 1, 4, 4) == 3 * q + 2 * kv + 2 * row
    assert flops.flash_bytes("dkv", 3, 2, 1, 4, 4) == 2 * q + 4 * kv + 2 * row


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert flops.roofline_seconds(10, 1000, peak) == (100.0, "memory")


def test_the_v5e_is_in_the_table_with_its_source():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v6 lite", "cpu", "_about", ""])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError, match="do not borrow"):
        flops.peaks(kind)


def test_mistral_7b_at_depth_two_is_the_mfu_the_ledger_implies():
    arch = MISTRAL.arch(BENCH.config("mistral-7b-d2"))
    per_tok = MISTRAL.train_flops_per_token(arch, 2048)
    # 29.04k tokens/s (ledger, PR 22) is about half the v5e's bf16 peak
    assert 0.45 < 29042.9 * per_tok / 197e12 < 0.60
