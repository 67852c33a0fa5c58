"""EvoformerAttention parity (reference analog:
``tests/unit/ops/deepspeed4science/test_DS4Sci_EvoformerAttention.py`` —
CUTLASS kernel vs a torch reference; here the Pallas bias-capable flash
kernel vs an exact jnp MSA attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.ops.evoformer_attn import (
    DS4Sci_EvoformerAttention, evoformer_attention)

B, N, S, H, D = 2, 3, 64, 4, 32


def _msa(rng):
    ks = jax.random.split(jax.random.PRNGKey(rng), 5)
    q = jax.random.normal(ks[0], (B, N, S, H, D))
    k = jax.random.normal(ks[1], (B, N, S, H, D))
    v = jax.random.normal(ks[2], (B, N, S, H, D))
    mask = (jax.random.uniform(ks[3], (B, N, 1, 1, S)) > 0.2)
    mask_bias = jnp.where(mask, 0.0, -1e9)
    pair = jax.random.normal(ks[4], (B, 1, H, S, S))
    return q, k, v, mask_bias, pair


def _reference(q, k, v, mask_bias=None, pair=None):
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", q, k) / np.sqrt(q.shape[-1])
    if mask_bias is not None:
        logits = logits + mask_bias[:, :, 0][:, :, None]  # [B,N,1,1,K]
    if pair is not None:
        logits = logits + pair  # [B,1,H,Q,K] broadcasts over N
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnhqk,bnkhd->bnqhd", p, v)


class TestEvoformerParity:
    def test_forward_both_biases(self):
        q, k, v, mb, pair = _msa(0)
        ref = _reference(q, k, v, mb, pair)
        got = evoformer_attention(q, k, v, [mb, pair], interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_forward_no_bias_and_alias(self):
        q, k, v, _, _ = _msa(1)
        ref = _reference(q, k, v)
        got = DS4Sci_EvoformerAttention(q, k, v, None, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_pair_bias_gradient_sums_over_rows(self):
        """dPair must flow through the fused backward and reduce over the N
        broadcast rows."""
        q, k, v, mb, pair = _msa(2)

        def loss(fn):
            def inner(q, k, v, pair):
                return (fn(q, k, v, pair) ** 2).sum()
            return jax.jit(jax.grad(inner, argnums=(0, 1, 2, 3)))(
                q, k, v, pair)

        g_got = loss(lambda q, k, v, p: evoformer_attention(
            q, k, v, [mb, p], interpret=True))
        g_ref = loss(lambda q, k, v, p: _reference(q, k, v, mb, p))
        for a, b in zip(g_got, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_mask_excludes_keys(self):
        """A fully-masked key must not influence the output."""
        q, k, v, _, _ = _msa(3)
        mask_bias = jnp.zeros((B, N, 1, 1, S)).at[:, :, :, :, 7].set(-1e9)
        out1 = evoformer_attention(q, k, v, [mask_bias, None], interpret=True)
        v2 = v.at[:, :, 7].set(123.0)  # perturb the masked key's value
        k2 = k.at[:, :, 7].set(-55.0)
        out2 = evoformer_attention(q, k2, v2, [mask_bias, None],
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-5)

    def test_bad_shapes_rejected(self):
        q, k, v, _, _ = _msa(4)
        with pytest.raises(ValueError):
            evoformer_attention(q[0], k[0], v[0])  # rank 4
        with pytest.raises(ValueError):
            evoformer_attention(q, k, v, [jnp.zeros((B, N, H, S, S))])
