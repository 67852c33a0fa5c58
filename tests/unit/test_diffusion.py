"""Diffusion family tests: SD-style VAE + conditional UNet (reference
``module_inject/containers/unet.py`` / ``vae.py`` serving surfaces;
``csrc/spatial`` fused bias-adds ride the conv paths here).

No ``diffusers`` in the environment, so parity is against first principles:
GroupNorm vs a manual reference, VAE shape/roundtrip contracts, UNet skip
bookkeeping at every resolution, timestep-embedding structure, and both
models training end-to-end through the engine protocol.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeedsyclsupport_tpu as ds
from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology
from deepspeedsyclsupport_tpu.models.diffusion import (
    AutoencoderKL, UNet2DCondition, UNetConfig, VAEConfig, group_norm,
    timestep_embedding)


class TestPrimitives:
    def test_group_norm_matches_manual(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 8))
        scale = jnp.arange(1.0, 9.0)
        bias = jnp.linspace(-1, 1, 8)
        got = np.asarray(group_norm(x, scale, bias, groups=2))
        xr = np.asarray(x).reshape(2, 4, 4, 2, 4)
        mean = xr.mean(axis=(1, 2, 4), keepdims=True)
        var = xr.var(axis=(1, 2, 4), keepdims=True)
        want = ((xr - mean) / np.sqrt(var + 1e-6)).reshape(2, 4, 4, 8)
        want = want * np.asarray(scale) + np.asarray(bias)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_timestep_embedding(self):
        e = timestep_embedding(jnp.array([0, 10]), 16)
        assert e.shape == (2, 16)
        np.testing.assert_allclose(np.asarray(e[0, :8]), 1.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(e[0, 8:]), 0.0, atol=1e-6)


class TestVAE:
    @pytest.fixture(scope="class")
    def vae(self):
        cfg = VAEConfig(base_channels=8, channel_mults=(1, 2),
                        latent_channels=4)
        model = AutoencoderKL(cfg)
        # (one program: a draw a leaf is one each otherwise)
        return model, jax.jit(model.init_params)(jax.random.PRNGKey(0))

    def test_encode_decode_shapes(self, vae):
        model, params = vae
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        # (each walk one program: eagerly every conv of it is one a shape)
        mean, logvar = jax.jit(model.encode)(params, x)
        # one downsample level (len(mults)-1 = 1) → /2 spatial
        assert mean.shape == (2, 8, 8, 4) and logvar.shape == mean.shape
        rec = jax.jit(model.decode)(params, mean)
        assert rec.shape == x.shape

    def test_trains_through_engine(self, vae):
        model, params = vae
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 8, 8, 3))
        try:
            engine, _, _, _ = ds.initialize(
                model=model, params=params,
                config={"train_batch_size": 8,
                        "train_micro_batch_size_per_gpu": 1,
                        "optimizer": {"type": "adam",
                                      "params": {"lr": 1e-3}}})
            losses = [float(engine.train_batch({"pixel_values": x})["loss"])
                      for _ in range(4)]
        finally:
            reset_world_topology()
        assert losses[-1] < losses[0]


class TestUNet:
    @pytest.fixture(scope="class")
    def unet(self):
        cfg = UNetConfig(base_channels=8, channel_mults=(1, 2),
                         attn_levels=(1,), num_heads=2,
                         cross_attention_dim=16)
        model = UNet2DCondition(cfg)
        return model, jax.jit(model.init_params)(jax.random.PRNGKey(0))

    @pytest.fixture(scope="class")
    def forward(self, unet):
        """``model.apply`` as one program a shape (eagerly every conv and
        norm of the walk is a program of its own: 40 s for two shapes)."""
        return jax.jit(unet[0].apply)

    def test_forward_shapes_all_resolutions(self, unet, forward):
        model, params = unet
        for hw in (8, 16):
            x = jax.random.normal(jax.random.PRNGKey(1), (2, hw, hw, 4))
            ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
            out = forward(params, x, jnp.array([3, 700]), ctx)
            assert out.shape == (2, hw, hw, 4)

    def test_conditioning_matters(self, unet, forward):
        """Cross-attention actually conditions the output."""
        model, params = unet
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 4))
        c1 = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 16))
        c2 = jax.random.normal(jax.random.PRNGKey(5), (1, 5, 16))
        t = jnp.array([100])
        o1 = forward(params, x, t, c1)
        o2 = forward(params, x, t, c2)
        assert float(jnp.abs(o1 - o2).max()) > 1e-6

    def test_timestep_matters(self, unet, forward):
        model, params = unet
        x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 8, 4))
        ctx = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 16))
        o1 = forward(params, x, jnp.array([1]), ctx)
        o2 = forward(params, x, jnp.array([999]), ctx)
        assert float(jnp.abs(o1 - o2).max()) > 1e-6

    def test_trains_through_engine(self, unet):
        model, params = unet
        lat = jax.random.normal(jax.random.PRNGKey(8), (8, 8, 8, 4))
        ctx = jax.random.normal(jax.random.PRNGKey(9), (8, 5, 16))
        batch = {"latents": lat, "encoder_hidden_states": ctx}
        try:
            engine, _, _, _ = ds.initialize(
                model=model, params=params,
                config={"train_batch_size": 8,
                        "train_micro_batch_size_per_gpu": 1,
                        "optimizer": {"type": "adam",
                                      "params": {"lr": 3e-3}},
                        "zero_optimization": {"stage": 1}})
            losses = [float(engine.train_batch(batch)["loss"])
                      for _ in range(10)]
        finally:
            reset_world_topology()
        # the DDPM objective resamples timesteps+noise per step, so single
        # steps are noisy — compare window means
        assert np.mean(losses[-3:]) < np.mean(losses[:3])
