"""A tiny synthetic HF ``olmoe`` checkpoint through ``checkpoint/hf.py``: the
tree it gives is the one the plain reference (``benchmark/families/olmoe.py``)
and the program expect — experts stacked in index order, the router turned to
``[d, E]``, the two projection norms' scales — and the config it reads says
what the published one says."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
import torch

from benchmark import spec
from deepspeedsyclsupport_tpu.checkpoint.hf import (config_from_hf,
                                                    load_hf_checkpoint)

HF = {"model_type": "olmoe", "vocab_size": 128, "hidden_size": 32,
      "intermediate_size": 16, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "num_experts": 6, "num_experts_per_tok": 2, "norm_topk_prob": False,
      "max_position_embeddings": 64, "rope_theta": 10000.0,
      "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
      "hidden_act": "silu", "clip_qkv": None, "attention_bias": False}
D, F, E, L, V = 32, 16, 6, 2, 128


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """(directory, the HF state dict as numpy)."""
    from safetensors.torch import save_file

    path = str(tmp_path_factory.mktemp("olmoe"))
    g = torch.Generator().manual_seed(0)
    w = lambda *shape: torch.randn(*shape, generator=g) * 0.2  # noqa: E731
    sd = {"model.embed_tokens.weight": w(V, D),
          "model.norm.weight": 1 + w(D), "lm_head.weight": w(V, D)}
    for i in range(L):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = 1 + w(D)
        sd[pre + "post_attention_layernorm.weight"] = 1 + w(D)
        for name in "qkvo":
            sd[pre + f"self_attn.{name}_proj.weight"] = w(D, D)
        sd[pre + "self_attn.q_norm.weight"] = 1 + w(D)
        sd[pre + "self_attn.k_norm.weight"] = 1 + w(D)
        sd[pre + "mlp.gate.weight"] = w(E, D)
        for e in range(E):
            ep = pre + f"mlp.experts.{e}."
            sd[ep + "gate_proj.weight"] = w(F, D)
            sd[ep + "up_proj.weight"] = w(F, D)
            sd[ep + "down_proj.weight"] = w(D, F)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(HF, f)
    save_file(sd, os.path.join(path, "model.safetensors"))
    return path, {k: v.numpy() for k, v in sd.items()}


def test_the_published_config_reads_as_the_preset():
    from deepspeedsyclsupport_tpu.models import get_config

    published = {
        "model_type": "olmoe", "attention_bias": False, "clip_qkv": None,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "norm_topk_prob": False,
        "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    cfg = config_from_hf(published)
    preset = get_config("olmoe-1b-7b")
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_layers", "num_heads", "num_kv_heads", "head_dim",
                "max_seq_len", "num_experts", "num_experts_per_tok",
                "norm_topk_prob", "qk_norm", "rope_theta", "rms_norm_eps",
                "tie_embeddings", "activation", "qkv_bias"):
        assert getattr(cfg, key) == getattr(preset, key), key
    with pytest.raises(ValueError, match="clip_qkv"):
        config_from_hf({**published, "clip_qkv": 8.0})


def test_the_loaded_tree_is_the_one_the_program_and_the_reference_expect(
        checkpoint):
    path, sd = checkpoint
    model, params = load_hf_checkpoint(path)
    cfg = model.config
    assert cfg.qk_norm and not cfg.norm_topk_prob and cfg.num_experts == E
    layers = params["layers"]
    # the same tree the program initialises: names and shapes
    want = model.init_params()
    import jax

    assert jax.tree_util.tree_map(jnp.shape, params) \
        == jax.tree_util.tree_map(jnp.shape, want)
    for i in range(L):
        pre = f"model.layers.{i}."
        # router orientation: HF's Linear holds [E, d], the tree [d, E]
        np.testing.assert_array_equal(
            np.asarray(layers["moe"]["router"][i]),
            sd[pre + "mlp.gate.weight"].T)
        for leaf, proj in (("q_norm", "q_norm"), ("k_norm", "k_norm")):
            np.testing.assert_array_equal(
                np.asarray(layers["attn"][leaf]["scale"][i]),
                sd[pre + f"self_attn.{proj}.weight"])
        # expert stacking order: tree index e IS the checkpoint's expert e
        for e in range(E):
            ep = pre + f"mlp.experts.{e}."
            for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                               ("w_down", "down_proj")):
                np.testing.assert_array_equal(
                    np.asarray(layers["moe"][leaf][i, e]),
                    sd[ep + name + ".weight"].T)


def test_the_reference_and_the_program_agree_on_the_loaded_weights(
        checkpoint):
    """A stacking or orientation fault that kept every shape (here d == q_dim
    and E != k) would still change the logits: the family's plain forward of
    the loaded tree against an independent numpy forward off the HF
    tensors, and the program's own forward against both."""
    path, sd = checkpoint
    model, params = load_hf_checkpoint(
        path, config_overrides={"attn_impl": "xla", "dtype": "float32",
                                "capacity_factor": float(E) / 2})
    ids = np.asarray([5, 99, 3, 41, 77, 8, 120, 64, 2], np.int32)
    family = spec.Bench().family(HF)
    ref = np.asarray(family.sequence_logits(family.arch(HF), params,
                                            jnp.asarray(ids)))
    np.testing.assert_allclose(ref, _numpy_forward(sd, ids), rtol=2e-4,
                               atol=2e-4)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def _numpy_forward(sd, ids):
    """HF ``modeling_olmoe`` in float64 numpy, straight off the tensors."""
    sd = {k: v.astype(np.float64) for k, v in sd.items()}
    s, h, hd = len(ids), 4, D // 4

    def rms(v, scale):
        return v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5) * scale

    def rope(v):               # [S, H, hd], rotate_half
        freqs = 1.0 / 10000.0 ** (np.arange(0, hd, 2) / hd)
        ang = np.arange(s)[:, None, None] * freqs
        c, sn = np.cos(ang), np.sin(ang)
        v1, v2 = v[..., :hd // 2], v[..., hd // 2:]
        return np.concatenate([v1 * c - v2 * sn, v2 * c + v1 * sn], -1)

    x = sd["model.embed_tokens.weight"][ids]
    for i in range(L):
        pre = f"model.layers.{i}."
        y = rms(x, sd[pre + "input_layernorm.weight"])
        q = rms(y @ sd[pre + "self_attn.q_proj.weight"].T,
                sd[pre + "self_attn.q_norm.weight"])
        k = rms(y @ sd[pre + "self_attn.k_proj.weight"].T,
                sd[pre + "self_attn.k_norm.weight"])
        v = (y @ sd[pre + "self_attn.v_proj.weight"].T).reshape(s, h, hd)
        q, k = rope(q.reshape(s, h, hd)), rope(k.reshape(s, h, hd))
        sc = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        sc = np.where(np.tril(np.ones((s, s), bool))[None], sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        x = x + np.einsum("hqk,khd->qhd", pr, v).reshape(s, D) \
            @ sd[pre + "self_attn.o_proj.weight"].T
        y = rms(x, sd[pre + "post_attention_layernorm.weight"])
        logits = y @ sd[pre + "mlp.gate.weight"].T
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        out = np.zeros_like(y)
        for t in range(s):
            for e in np.argsort(-probs[t])[:HF["num_experts_per_tok"]]:
                ep = pre + f"mlp.experts.{e}."
                g = y[t] @ sd[ep + "gate_proj.weight"].T
                u = y[t] @ sd[ep + "up_proj.weight"].T
                out[t] += probs[t, e] * (
                    (g / (1 + np.exp(-g)) * u) @ sd[ep + "down_proj.weight"].T)
        x = x + out
    return rms(x, sd["model.norm.weight"]) @ sd["lm_head.weight"].T
