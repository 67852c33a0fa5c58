"""``ModelConfig`` with a sparse-attention indexer over LATENT attention
(ISSUE 65): what it accepts, what it still refuses, the ``glm-5`` preset's
parameter count against the issue's arithmetic, and how ``init_params``
seeds what only this combination has."""
import jax
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.models import ModelConfig, build_model, get_config
from deepspeedsyclsupport_tpu.models.transformer import (
    INDEX_Q_NORM_SPREAD, SELECTED_LATENT_WRITE)

LATENT = dict(kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=12,
              qk_rope_head_dim=8, v_head_dim=16)
INDEXER = dict(index_topk=8, index_heads=2, index_head_dim=16)
TINY = dict(hidden_size=64, intermediate_size=96, num_layers=2, num_heads=4,
            num_kv_heads=4, head_dim=20, vocab_size=128, max_seq_len=64)


def test_an_indexer_over_latent_attention_builds():
    cfg = ModelConfig(**TINY, **LATENT, **INDEXER, index_rope_dim=8,
                      index_q_latent=True)
    assert (cfg.latent_kv_dim, cfg.index_topk, cfg.index_rope_dim) == (
        24, 8, 8)
    # ... and over K and V as before, rotating its whole width
    plain = ModelConfig(**TINY, **INDEXER)
    assert (plain.index_rope_dim, plain.index_q_latent) == (0, False)


@pytest.mark.parametrize("what, kw", [
    ("window", dict(sliding_window=16)),
    ("layer_pattern", dict(layer_pattern="**")),
    ("looped stack", dict(total_ut_steps=2)),
    ("hyper-connection", dict(hc_mult=2)),
    ("period of attention kinds", dict(
        attn_period=((4, "rope"), (None, "none")))),
    ("index_heads", dict(index_heads=0)),
    ("rotary positions", dict(pos_embed="none"))])
def test_the_indexer_still_refuses_what_it_is_not_written_for(what, kw):
    for latent in ({}, LATENT):
        with pytest.raises(ValueError, match="index_topk|layer_pattern|"
                                             "attn_period"):
            ModelConfig(**{**TINY, **latent, **INDEXER, **kw})


@pytest.mark.parametrize("kw, says", [
    (dict(index_rope_dim=7), "index_rope_dim 7"),
    (dict(index_rope_dim=18), "index_rope_dim 18"),
    (dict(index_q_latent=True), "index_q_latent")])
def test_the_new_fields_say_what_they_need(kw, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig(**{**TINY, **INDEXER, **kw})


def test_the_presets_parameters_are_the_issues_arithmetic():
    whole = get_config("glm-5")
    d, v = 6144, 154880
    attn = (d * 2048 + 2048 * 64 * 256 + d * 576 + 512 * 64 * (192 + 256)
            + 64 * 256 * d)
    index = 2048 * 32 * 128 + d * 128 + d * 32 + 2 * 128
    expert = 3 * d * 2048
    assert attn / 1e6 == pytest.approx(165.02, abs=0.01)
    assert index / 1e6 == pytest.approx(9.37, abs=0.01)
    assert expert / 1e6 == pytest.approx(37.75, abs=0.01)
    assert 2 * v * d / 1e9 == pytest.approx(1.903, abs=0.001)
    dense = attn + index + 3 * d * 12288 + 2 * d
    moe = attn + index + 2 * d + d * 256 + 257 * expert
    assert whole.param_count() == 3 * dense + 75 * moe + 2 * v * d + d
    # this chip's cut: one dense layer and four expert layers of 16 held
    cut = get_config("glm-5", num_layers=5, first_k_dense_replace=1,
                     num_experts_held=16)
    here = attn + index + 2 * d + d * 256 + 17 * expert
    assert here / 1e6 == pytest.approx(817.7, abs=0.1)
    assert cut.param_count() == dense + 4 * here + 2 * v * d + d
    assert cut.param_count() * 2 / 2**30 == pytest.approx(10.38, abs=0.01)


def test_what_init_params_draws_for_an_indexer_over_a_latent():
    model = build_model("glm-5", **TINY, **LATENT, **INDEXER, index_rope_dim=8, moe_intermediate_size=32,
                        num_experts=8, num_experts_per_tok=3,
                        num_experts_held=4, first_k_dense_replace=1,
                        dtype="float32")
    params = jax.jit(model.init_params)(jax.random.PRNGKey(1))
    for name, layers in (("dense_layers", 1), ("layers", 1)):
        attn = params[name]["attn"]
        # the queries read the 24-wide query latent, key and weights the row
        assert attn["w_qi"].shape == (layers, 24, 2 * 16)
        assert attn["w_ki"].shape == (layers, 64, 16)
        assert attn["w_w"].shape == (layers, 64, 2)
        # the latent's norm scale is spread at unit root mean square, so
        # that the norm moves the selection (INDEX_Q_NORM_SPREAD)
        scale = np.asarray(attn["q_norm"]["scale"])
        assert np.sqrt((scale ** 2).mean(-1)) == pytest.approx(1.0, abs=1e-5)
        assert np.log(scale).std() == pytest.approx(INDEX_Q_NORM_SPREAD,
                                                    rel=0.3)
        assert np.asarray(attn["kv_norm"]["scale"]).min() == 1.0
    # W_o behind a selection is drawn at the selected attention's share
    plain = build_model("glm-5", **TINY, **LATENT, index_topk=0, index_q_latent=False,
                        moe_intermediate_size=32, num_experts=8,
                        num_experts_per_tok=3, first_k_dense_replace=1,
                        dtype="float32")
    other = jax.jit(plain.init_params)(jax.random.PRNGKey(1))
    assert np.asarray(other["layers"]["attn"]["q_norm"]["scale"]).min() == 1.0
    assert "w_qi" not in other["layers"]["attn"]
    ratio = float(np.std(params["layers"]["attn"]["wo"])
                  / np.std(other["layers"]["attn"]["wo"]))
    assert ratio == pytest.approx(SELECTED_LATENT_WRITE, rel=0.1)
