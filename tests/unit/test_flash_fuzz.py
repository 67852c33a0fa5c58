"""Seeded randomized parity sweep for the flash kernel family.

The reference proves every CUDA kernel against a torch oracle at a handful
of hand-picked shapes (SURVEY.md §4); this sweep drives the SAME parity
check across randomized configurations — shapes, GQA ratios, unaligned
lengths, cross-attention offsets, windows, packed segments — so mask/
block-edge regressions can't hide in untested corners. Deterministic
(seeded), CPU-interpret sized."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeedsyclsupport_tpu.ops.flash_attention import flash_attention


def dense_ref(q, k, v, causal, segment_ids=None, window=None):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    if kvh != h:
        rep = h // kvh
        kf = jnp.repeat(kf, rep, axis=2)
        vf = jnp.repeat(vf, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(d)
    mask = jnp.ones((b, 1, sq, skv), bool)
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    if causal:
        mask = jnp.logical_and(mask, (kpos <= qpos)[None, None])
    if window is not None:
        mask = jnp.logical_and(mask, (qpos - kpos < window)[None, None])
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        mask = jnp.logical_and(mask, same[:, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


CASES = 12


@pytest.mark.parametrize("case", range(CASES))
def test_flash_parity_randomized(case):
    rng = np.random.RandomState(1000 + case)
    b = int(rng.randint(1, 3))
    h = int(rng.choice([2, 4, 8]))
    kvh = int(rng.choice([g for g in (1, 2, h) if h % g == 0]))
    d = int(rng.choice([16, 32, 64]))
    sq = int(rng.randint(17, 200))
    self_attn = bool(rng.rand() < 0.6)
    skv = sq if self_attn else int(sq + rng.randint(0, 100))
    causal = bool(rng.rand() < 0.7)
    window = (int(rng.randint(8, sq)) if causal and rng.rand() < 0.3
              else None)
    use_segments = self_attn and rng.rand() < 0.4
    block = int(rng.choice([64, 128]))

    kq, kk, kv_, = jax.random.split(jax.random.PRNGKey(case), 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, kvh, d), jnp.float32)
    v = jax.random.normal(kv_, (b, skv, kvh, d), jnp.float32)
    seg = None
    if use_segments:
        # random packing: 1-4 segments in ascending order
        cuts = np.sort(rng.choice(np.arange(1, sq), size=rng.randint(0, 3),
                                  replace=False))
        seg = jnp.asarray(np.searchsorted(cuts, np.arange(sq),
                                          side="right"))[None, :]
        seg = jnp.broadcast_to(seg, (b, sq))

    # (each side ONE program: eagerly every op of it is one a shape, and
    # every case draws shapes of its own)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, segment_ids=seg, window=window,
        block_q=block, block_k=block))(q, k, v)
    want = jax.jit(lambda q, k, v: dense_ref(
        q, k, v, causal, segment_ids=seg, window=window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4,
                               err_msg=f"case {case}: b={b} sq={sq} "
                                       f"skv={skv} h={h}/{kvh} d={d} "
                                       f"causal={causal} window={window} "
                                       f"seg={use_segments} block={block}")


@pytest.mark.parametrize("case", range(6))
def test_flash_grad_parity_randomized(case):
    rng = np.random.RandomState(2000 + case)
    h = int(rng.choice([2, 4]))
    kvh = int(rng.choice([g for g in (1, h) if h % g == 0]))
    d = int(rng.choice([16, 32]))
    sq = int(rng.randint(17, 120))
    causal = bool(rng.rand() < 0.7)

    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(100 + case), 3)
    q = jax.random.normal(kq, (1, sq, h, d), jnp.float32)
    k = jax.random.normal(kk, (1, sq, kvh, d), jnp.float32)
    v = jax.random.normal(kv_, (1, sq, kvh, d), jnp.float32)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * v.sum(2, keepdims=True)).sum()

    g_got = jax.jit(jax.grad(loss(lambda *a: flash_attention(
        *a, causal=causal, block_q=64, block_k=64)), (0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(loss(lambda *a: dense_ref(*a, causal)),
                              (0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("qkv", g_got, g_want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
            err_msg=f"case {case} d{name}: sq={sq} h={h}/{kvh} d={d} "
                    f"causal={causal}")
