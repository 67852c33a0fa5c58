"""Encoder-architecture ingestion parity: BERT / DistilBERT / CLIP vs the
real HuggingFace implementations (reference per-arch policies:
``deepspeed/module_inject/containers/bert.py``, ``distil_bert.py``,
``clip.py``), plus an engine-protocol training smoke: BERT-base under ZeRO-1
(optimizer state sharded over the data axis, parameters replicated).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeedsyclsupport_tpu.checkpoint.hf import (
    load_hf_clip_checkpoint, load_hf_encoder_checkpoint)
from deepspeedsyclsupport_tpu.models.encoder import (BertModel, CLIPModel,
                                                     EncoderConfig)

V, D, L, H, SEQ = 128, 32, 2, 4, 16


def _ids(rng, b=2, s=SEQ, v=V):
    return np.asarray(rng.integers(1, v - 1, size=(b, s)), np.int32)


class TestBertParity:
    def _save(self, tmp_path):
        from transformers import BertConfig, BertForMaskedLM

        hf = BertForMaskedLM(BertConfig(
            vocab_size=V, hidden_size=D, num_hidden_layers=L,
            num_attention_heads=H, intermediate_size=48,
            max_position_embeddings=64, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        hf.eval()
        hf.save_pretrained(tmp_path)
        return hf

    def test_mlm_logits_parity(self, tmp_path):
        hf = self._save(tmp_path)
        model, params = load_hf_encoder_checkpoint(str(tmp_path))
        rng = np.random.default_rng(0)
        ids = _ids(rng)
        mask = np.ones_like(ids)
        mask[:, -3:] = 0  # right padding
        tt = np.zeros_like(ids)
        tt[:, SEQ // 2:] = 1
        with torch.no_grad():
            theirs = hf(input_ids=torch.tensor(ids, dtype=torch.long),
                        attention_mask=torch.tensor(mask, dtype=torch.long),
                        token_type_ids=torch.tensor(tt, dtype=torch.long)
                        ).logits.numpy()
        ours = np.asarray(model.apply(params, jnp.asarray(ids),
                                      jnp.asarray(mask), jnp.asarray(tt)))
        valid = mask.astype(bool)
        np.testing.assert_allclose(ours[valid], theirs[valid],
                                   rtol=2e-4, atol=2e-4)

    def test_pooler_parity(self, tmp_path):
        from transformers import BertConfig, BertModel as HFBertModel

        cfg = BertConfig(
            vocab_size=V, hidden_size=D, num_hidden_layers=L,
            num_attention_heads=H, intermediate_size=48,
            max_position_embeddings=64, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        hf = HFBertModel(cfg)
        hf.eval()
        hf.save_pretrained(tmp_path)
        model, params = load_hf_encoder_checkpoint(str(tmp_path))
        ids = _ids(np.random.default_rng(1))
        with torch.no_grad():
            theirs = hf(torch.tensor(ids, dtype=torch.long)
                        ).pooler_output.numpy()
        ours = np.asarray(model.pooled(params, jnp.asarray(ids)))
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


class TestDistilBertParity:
    def test_mlm_logits_parity(self, tmp_path):
        from transformers import DistilBertConfig, DistilBertForMaskedLM

        hf = DistilBertForMaskedLM(DistilBertConfig(
            vocab_size=V, dim=D, n_layers=L, n_heads=H, hidden_dim=48,
            max_position_embeddings=64, dropout=0.0, attention_dropout=0.0))
        hf.eval()
        hf.save_pretrained(tmp_path)
        model, params = load_hf_encoder_checkpoint(str(tmp_path))
        assert model.config.type_vocab_size == 0
        ids = _ids(np.random.default_rng(2))
        with torch.no_grad():
            theirs = hf(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        ours = np.asarray(model.apply(params, jnp.asarray(ids)))
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


class TestEncoderOnlyExports:
    def test_distilbert_encoder_only(self, tmp_path):
        """DistilBertModel (no MLM head) exports drop the 'distilbert.'
        prefix — the hidden states must still load and match."""
        from transformers import DistilBertConfig
        from transformers import DistilBertModel as HFDistilBertModel

        hf = HFDistilBertModel(DistilBertConfig(
            vocab_size=V, dim=D, n_layers=L, n_heads=H, hidden_dim=48,
            max_position_embeddings=64, dropout=0.0, attention_dropout=0.0))
        hf.eval()
        hf.save_pretrained(tmp_path)
        model, params = load_hf_encoder_checkpoint(str(tmp_path))
        ids = _ids(np.random.default_rng(7))
        with torch.no_grad():
            theirs = hf(torch.tensor(ids, dtype=torch.long)
                        ).last_hidden_state.numpy()
        ours = np.asarray(model.encode(params, jnp.asarray(ids)))
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


class TestCLIPParity:
    def _save(self, tmp_path):
        from transformers import CLIPConfig as HFCLIPConfig
        from transformers import CLIPModel as HFCLIPModel

        cfg = HFCLIPConfig.from_text_vision_configs(
            transformers.CLIPTextConfig(
                vocab_size=V, hidden_size=D, intermediate_size=48,
                num_hidden_layers=L, num_attention_heads=H,
                max_position_embeddings=32, eos_token_id=V - 1,
                attention_dropout=0.0),
            transformers.CLIPVisionConfig(
                hidden_size=D, intermediate_size=48, num_hidden_layers=L,
                num_attention_heads=H, image_size=32, patch_size=8,
                attention_dropout=0.0),
            projection_dim=24)
        hf = HFCLIPModel(cfg)
        hf.eval()
        hf.save_pretrained(tmp_path)
        return hf

    def test_tower_and_logit_parity(self, tmp_path):
        hf = self._save(tmp_path)
        model, params = load_hf_clip_checkpoint(str(tmp_path))
        rng = np.random.default_rng(3)
        ids = _ids(rng, b=3, s=12)
        ids[:, -1] = V - 1  # eos
        pix = np.asarray(rng.normal(size=(2, 3, 32, 32)), np.float32)
        with torch.no_grad():
            t_ref = hf.get_text_features(
                torch.tensor(ids, dtype=torch.long)).numpy()
            i_ref = hf.get_image_features(torch.tensor(pix)).numpy()
            out = hf(input_ids=torch.tensor(ids, dtype=torch.long),
                     pixel_values=torch.tensor(pix))
            lpi_ref = out.logits_per_image.numpy()
        t_ours = np.asarray(model.apply_text(params, jnp.asarray(ids)))
        i_ours = np.asarray(model.apply_image(params, jnp.asarray(pix)))
        np.testing.assert_allclose(t_ours, t_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(i_ours, i_ref, rtol=2e-4, atol=2e-4)
        _, lpi_ours = model.apply(params, jnp.asarray(ids), jnp.asarray(pix))
        np.testing.assert_allclose(np.asarray(lpi_ours), lpi_ref,
                                   rtol=2e-4, atol=2e-4)


class TestEncoderTraining:
    def test_bert_zero1_engine(self):
        """BERT + ZeRO-1 (stage 1, Adam) through the engine."""
        import deepspeedsyclsupport_tpu as ds
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)

        cfg = EncoderConfig(vocab_size=V, hidden_size=D, num_layers=L,
                            num_heads=H, intermediate_size=48,
                            max_seq_len=32)
        model = BertModel(cfg)
        rng = np.random.default_rng(4)
        ids = _ids(rng, b=8, s=16)
        labels = np.full_like(ids, -100)
        labels[:, 2:6] = ids[:, 2:6]  # the masked positions to predict
        batch = {"input_ids": jnp.asarray(ids),
                 "labels": jnp.asarray(labels)}
        try:
            engine, _, _, _ = ds.initialize(
                model=model,
                config={"train_batch_size": 8,
                        "train_micro_batch_size_per_gpu": 1,
                        "optimizer": {"type": "adam",
                                      "params": {"lr": 5e-3}},
                        "zero_optimization": {"stage": 1}})
            losses = [float(engine.train_batch(batch)["loss"])
                      for _ in range(5)]
        finally:
            reset_world_topology()
        assert losses[-1] < losses[0]

    def test_clip_contrastive_training(self):
        """CLIP towers train end-to-end on the contrastive loss."""
        from deepspeedsyclsupport_tpu.models.encoder import CLIPConfig
        import optax

        cfg = CLIPConfig(
            text=EncoderConfig(vocab_size=V, hidden_size=D,
                               intermediate_size=48, num_layers=L,
                               num_heads=H, max_seq_len=16,
                               type_vocab_size=0, layer_norm_eps=1e-5,
                               activation="quick_gelu", norm_position="pre",
                               causal=True),
            vision=EncoderConfig(vocab_size=0, hidden_size=D,
                                 intermediate_size=48, num_layers=L,
                                 num_heads=H, type_vocab_size=0,
                                 layer_norm_eps=1e-5,
                                 activation="quick_gelu",
                                 norm_position="pre", image_size=16,
                                 patch_size=8),
            projection_dim=16, eos_token_id=V - 1)
        model = CLIPModel(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        batch = {"input_ids": jnp.asarray(_ids(rng, b=4, s=8)),
                 "pixel_values": jnp.asarray(
                     rng.normal(size=(4, 3, 16, 16)), jnp.float32)}
        tx = optax.adam(1e-3)
        opt = tx.init(params)

        @jax.jit
        def step(p, o):
            (l, _), g = jax.value_and_grad(
                lambda pp: model.loss(pp, batch), has_aux=True)(p)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, l

        losses = []
        for _ in range(5):
            params, opt, l = step(params, opt)
            losses.append(float(l))
        assert losses[-1] < losses[0]


class TestMegatronIngestion:
    """Megatron-LM GPT checkpoint ingestion (reference
    ``module_inject/containers/megatron_gpt.py``): a tiny GPT-2 is
    re-packed into the megatron-v2 per-head fused-qkv state-dict layout,
    loaded through ``load_megatron_checkpoint``, and must reproduce the
    torch logits — the strongest check of the per-head qkv decode."""

    def test_megatron_logits_parity(self, tmp_path):
        from transformers import GPT2Config, GPT2LMHeadModel

        from deepspeedsyclsupport_tpu.checkpoint.hf import (
            load_megatron_checkpoint)

        hd = D // H
        hf = GPT2LMHeadModel(GPT2Config(
            vocab_size=V, n_embd=D, n_layer=L, n_head=H, n_positions=64,
            n_inner=48, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0))
        hf.eval()
        sd = hf.state_dict()

        def mega_qkv(w_conv1d):
            # Conv1D [d, 3d] (q|k|v cols) → megatron per-head [3d, d] rows
            q, k, v = np.split(np.asarray(w_conv1d), 3, axis=1)
            stacked = np.stack([q.T.reshape(H, hd, D), k.T.reshape(H, hd, D),
                                v.T.reshape(H, hd, D)], axis=1)
            return stacked.reshape(3 * D, D)

        def mega_qkv_bias(b):
            q, k, v = np.split(np.asarray(b), 3)
            return np.stack([q.reshape(H, hd), k.reshape(H, hd),
                             v.reshape(H, hd)], axis=1).reshape(-1)

        enc = {}
        for i in range(L):
            g = f"transformer.h.{i}."
            m = f"layers.{i}."
            enc[m + "input_layernorm.weight"] = sd[g + "ln_1.weight"]
            enc[m + "input_layernorm.bias"] = sd[g + "ln_1.bias"]
            enc[m + "self_attention.query_key_value.weight"] = torch.tensor(
                mega_qkv(sd[g + "attn.c_attn.weight"]))
            enc[m + "self_attention.query_key_value.bias"] = torch.tensor(
                mega_qkv_bias(sd[g + "attn.c_attn.bias"]))
            enc[m + "self_attention.dense.weight"] = \
                sd[g + "attn.c_proj.weight"].T.contiguous()
            enc[m + "self_attention.dense.bias"] = sd[g + "attn.c_proj.bias"]
            enc[m + "post_attention_layernorm.weight"] = sd[g + "ln_2.weight"]
            enc[m + "post_attention_layernorm.bias"] = sd[g + "ln_2.bias"]
            enc[m + "mlp.dense_h_to_4h.weight"] = \
                sd[g + "mlp.c_fc.weight"].T.contiguous()
            enc[m + "mlp.dense_h_to_4h.bias"] = sd[g + "mlp.c_fc.bias"]
            enc[m + "mlp.dense_4h_to_h.weight"] = \
                sd[g + "mlp.c_proj.weight"].T.contiguous()
            enc[m + "mlp.dense_4h_to_h.bias"] = sd[g + "mlp.c_proj.bias"]
        enc["final_layernorm.weight"] = sd["transformer.ln_f.weight"]
        enc["final_layernorm.bias"] = sd["transformer.ln_f.bias"]
        ckpt = {"model": {"language_model": {
            "embedding": {
                "word_embeddings": {"weight": sd["transformer.wte.weight"]},
                "position_embeddings": {
                    "weight": sd["transformer.wpe.weight"]}},
            "encoder": enc}}}
        path = tmp_path / "model_optim_rng.pt"
        torch.save(ckpt, str(path))

        # gpt2 uses the tanh gelu ("gelu_new") — override the loader default
        model, params = load_megatron_checkpoint(
            str(path), num_heads=H,
            config_overrides={"activation": "gelu", "dtype": "float32"})
        ids = _ids(np.random.default_rng(11))
        with torch.no_grad():
            theirs = hf(torch.tensor(ids, dtype=torch.long)).logits.numpy()
        ours = np.asarray(model.apply(
            jax.tree_util.tree_map(jnp.asarray, params), jnp.asarray(ids)))
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


class TestEncoderServing:
    def test_bert_through_init_inference(self, tmp_path):
        """Encoder serving through the v1 engine (the reference serves BERT
        via kernel injection — here TP-sharded placement + jitted apply)."""
        from transformers import BertConfig, BertForMaskedLM

        from deepspeedsyclsupport_tpu.inference import init_inference

        hf = BertForMaskedLM(BertConfig(
            vocab_size=V, hidden_size=D, num_hidden_layers=L,
            num_attention_heads=H, intermediate_size=48,
            max_position_embeddings=64, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        hf.eval()
        hf.save_pretrained(tmp_path)
        model, params = load_hf_encoder_checkpoint(str(tmp_path))
        eng = init_inference(model=model, params=params,
                             config={"dtype": "fp32",
                                     "tensor_parallel": {"tp_size": 2}})
        ids = _ids(np.random.default_rng(13))
        mask = np.ones_like(ids)
        with torch.no_grad():
            theirs = hf(input_ids=torch.tensor(ids, dtype=torch.long)
                        ).logits.numpy()
        ours = np.asarray(eng.forward(jnp.asarray(ids), jnp.asarray(mask)))
        np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)
