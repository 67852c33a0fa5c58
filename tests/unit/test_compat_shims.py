"""Import-path compatibility shims: the module paths ported reference
scripts import (``deepspeed.pipe``, ``deepspeed.moe.layer``,
``deepspeed.ops.adam``, ``deepspeed.checkpointing``) must exist and
resolve onto the TPU-native implementations."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


class TestCompatShims:
    def test_pipe_module_path(self):
        from deepspeedsyclsupport_tpu.pipe import (PipelineModule,
                                                   TrainSchedule)
        from deepspeedsyclsupport_tpu.parallel.pipeline import (
            PipelineModule as Real)

        assert PipelineModule is Real
        assert len(list(TrainSchedule(4, 2, 0))) > 0

    def test_ops_adam_builds_optax(self):
        from deepspeedsyclsupport_tpu.ops.adam import (DeepSpeedCPUAdam,
                                                       FusedAdam)

        params = {"w": jnp.full((4,), 2.0)}
        for factory in (FusedAdam, DeepSpeedCPUAdam):
            tx = factory(lr=0.1, weight_decay=0.0)
            st = tx.init(params)
            g = {"w": jnp.ones((4,))}
            upd, _ = tx.update(g, st, params)
            # first adam step ≈ -lr * sign(g)
            np.testing.assert_allclose(np.asarray(upd["w"]), -0.1,
                                       rtol=1e-3)

    def test_checkpointing_surface(self):
        from deepspeedsyclsupport_tpu import checkpointing

        checkpointing.reset()
        assert not checkpointing.is_configured()
        checkpointing.configure(partition_activations=True)
        assert checkpointing.is_configured()

        # remat must preserve gradients exactly
        def f(x):
            return jnp.sum(jnp.tanh(x) ** 2)

        x = jnp.linspace(-1, 1, 8)
        g_plain = jax.grad(f)(x)
        g_ckpt = jax.grad(
            lambda v: checkpointing.checkpoint(f, v))(x)
        np.testing.assert_allclose(np.asarray(g_ckpt), np.asarray(g_plain),
                                   rtol=1e-6)
        checkpointing.reset()

    def test_moe_layer_maps_to_config(self):
        from deepspeedsyclsupport_tpu.models import build_model
        from deepspeedsyclsupport_tpu.moe.layer import MoE

        spec = MoE(hidden_size=64, num_experts=4, k=2, capacity_factor=1.5)
        model = build_model("tiny", **spec.model_config_kwargs())
        assert model.config.num_experts == 4
        assert model.config.num_experts_per_tok == 2
        params = model.init_params(jax.random.PRNGKey(0))
        assert "moe" in jax.tree_util.tree_map(lambda x: 0,
                                               params)["layers"]


class TestDeepSpeedTransformerLayer:
    """ops/transformer.py (reference DeepSpeedTransformerLayer over the
    csrc/transformer CUDA kernels — here the shared encoder tower)."""

    def test_post_ln_matches_bert_block(self):
        """post-LN config must equal one layer of the BERT tower (the
        arrangement BertForPreTraining + the reference layer share)."""
        from deepspeedsyclsupport_tpu.models.encoder import (EncoderConfig,
                                                             tower_forward)
        from deepspeedsyclsupport_tpu.ops.transformer import (
            DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)

        cfg = DeepSpeedTransformerConfig(hidden_size=32, heads=4,
                                         intermediate_size=48,
                                         pre_layer_norm=False)
        layer = DeepSpeedTransformerLayer(cfg)
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        got = layer(params, x)
        want = tower_forward(
            EncoderConfig(vocab_size=0, hidden_size=32, num_heads=4,
                          intermediate_size=48, type_vocab_size=0,
                          layer_norm_eps=1e-12, activation="gelu_exact",
                          norm_position="post"), params, x, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_pre_vs_post_differ_and_mask_isolates(self):
        from deepspeedsyclsupport_tpu.ops.transformer import (
            DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)

        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 32))
        outs = {}
        for pre in (True, False):
            layer = DeepSpeedTransformerLayer(DeepSpeedTransformerConfig(
                hidden_size=32, heads=4, pre_layer_norm=pre))
            p = layer.init_params(jax.random.PRNGKey(0))
            outs[pre] = np.asarray(layer(p, x))
        assert np.abs(outs[True] - outs[False]).max() > 1e-3
        # padding isolation: changing a masked token leaves valid rows alone
        layer = DeepSpeedTransformerLayer(DeepSpeedTransformerConfig(
            hidden_size=32, heads=4))
        p = layer.init_params(jax.random.PRNGKey(0))
        mask = np.ones((2, 8), np.int32)
        mask[:, -2:] = 0
        x2 = np.asarray(x).copy()
        x2[:, -1] += 100.0
        a = np.asarray(layer(p, jnp.asarray(x), jnp.asarray(mask)))
        b = np.asarray(layer(p, jnp.asarray(x2), jnp.asarray(mask)))
        np.testing.assert_allclose(a[:, :6], b[:, :6], rtol=1e-5, atol=1e-5)

    def test_default_intermediate_and_return_tuple(self):
        from deepspeedsyclsupport_tpu.ops.transformer import (
            DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)

        cfg = DeepSpeedTransformerConfig(hidden_size=32, heads=4,
                                         return_tuple=True)
        assert cfg.intermediate_size == 128
        layer = DeepSpeedTransformerLayer(cfg)
        p = layer.init_params(jax.random.PRNGKey(0))
        out = layer(p, jnp.zeros((1, 4, 32)))
        assert isinstance(out, tuple) and out[0].shape == (1, 4, 32)

    def test_dropout_and_top_level_alias(self):
        import deepspeedsyclsupport_tpu as deepspeed

        cfg = deepspeed.DeepSpeedTransformerConfig(
            hidden_size=32, heads=4, hidden_dropout_ratio=0.5,
            initializer_range=0.01)
        layer = deepspeed.DeepSpeedTransformerLayer(cfg)
        p = layer.init_params(jax.random.PRNGKey(0))
        # initializer_range reaches the weights
        assert float(np.abs(np.asarray(
            jax.tree_util.tree_leaves(p)[0])).std()) < 0.02
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        eval_out = np.asarray(layer(p, x))
        train_out = np.asarray(layer(p, x, rng=jax.random.PRNGKey(2)))
        assert np.abs(eval_out - train_out).max() > 1e-4  # dropout active
        # eval (no rng) is deterministic
        np.testing.assert_array_equal(eval_out, np.asarray(layer(p, x)))
