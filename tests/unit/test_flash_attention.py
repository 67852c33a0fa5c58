"""Pallas flash attention: kernel-vs-reference parity, fwd + grad (the
CUDA-vs-torch parity pattern of the reference's kernel tests, SURVEY.md §4),
run in interpret mode on the CPU sim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.models.layers import reference_attention
from deepspeedsyclsupport_tpu.ops.flash_attention import flash_attention


def _qkv(rng, b=2, sq=256, skv=None, h=4, kvh=None, d=32, dtype=jnp.float32):
    skv = skv if skv is not None else sq
    kvh = kvh if kvh is not None else h
    ks = jax.random.split(jax.random.PRNGKey(rng), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, kvh, d), dtype)
    return q, k, v


class TestFlashForwardParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_basic(self, causal):
        q, k, v = _qkv(0)
        ref = reference_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(1, h=8, kvh=2)
        ref = reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_unaligned_lengths(self):
        # sequence not a multiple of the block: pad region must be masked
        q, k, v = _qkv(2, sq=200, skv=200)
        ref = reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cross_lengths_causal_offset(self):
        # Skv > Sq: queries sit at the end (chunked prefill shape)
        q, k, v = _qkv(3, sq=128, skv=384)
        ref = reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_segment_ids(self):
        q, k, v = _qkv(4, sq=256)
        seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 64, axis=1).reshape(1, 256)
                          .repeat(2, axis=0))
        ref = reference_attention(q, k, v, causal=True, segment_ids=seg)
        got = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              interpret=True, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(5, dtype=jnp.bfloat16)
        ref = reference_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestFlashGradParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(6, sq=256, d=32)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=128, block_k=128)
            return jnp.sum(o * jnp.cos(o))

        def loss_ref(q, k, v):
            o = reference_attention(q, k, v, causal=causal)
            return jnp.sum(o * jnp.cos(o))

        g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_grads_gqa_segments(self):
        q, k, v = _qkv(7, sq=256, h=8, kvh=2)
        seg = jnp.asarray(np.repeat([[0, 1]], 128, axis=1).reshape(1, 256)
                          .repeat(2, axis=0))

        def loss(fn):
            def inner(q, k, v):
                o = fn(q, k, v)
                return jnp.sum(jnp.tanh(o))
            return inner

        g_flash = jax.jit(jax.grad(
            loss(lambda q, k, v: flash_attention(
                q, k, v, causal=True, segment_ids=seg, interpret=True,
                block_q=128, block_k=128)), argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(
            loss(lambda q, k, v: reference_attention(
                q, k, v, causal=True, segment_ids=seg)),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_grad_under_jit_and_unaligned(self):
        q, k, v = _qkv(8, sq=200)

        @jax.jit
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, interpret=True,
                                block_q=128, block_k=128)
            return jnp.sum(o ** 2)

        g = jax.grad(loss)(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                reference_attention(q, k, v, causal=True) ** 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)


class TestCachedDecodeFlash:
    """KV-cache attention through the kernel (v1 prefill/decode): slot-space
    masks mapped to position arrays + kv segment ids must match the exact
    reference for chunked prefill and single-token decode."""

    def _data(self, b=2, sq=4, skv=32, h=4, kvh=2, d=16, seed=0):
        rng = jax.random.PRNGKey(seed)
        kq, kk, kv_ = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (b, sq, h, d), jnp.float32)
        k = jax.random.normal(kk, (b, skv, kvh, d), jnp.float32)
        v = jax.random.normal(kv_, (b, skv, kvh, d), jnp.float32)
        return q, k, v

    def test_positions_below_parity(self):
        from deepspeedsyclsupport_tpu.models.layers import (
            _cached_flash_attention, reference_attention)

        q, k, v = self._data()
        # chunk of 4 queries written at slots 10..13 → see slots <= own
        kv_below = jnp.asarray([[11, 12, 13, 14], [11, 12, 13, 14]],
                               jnp.int32)
        want = reference_attention(q, k, v, causal=False,
                                   kv_positions_below=kv_below)
        got = _cached_flash_attention(q, k, v, False, kv_below, None,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_positions_below_with_kv_mask_parity(self):
        from deepspeedsyclsupport_tpu.models.layers import (
            _cached_flash_attention, reference_attention)

        q, k, v = self._data()
        kv_below = jnp.asarray([[21, 22, 23, 24], [21, 22, 23, 24]],
                               jnp.int32)
        # ragged right-padding: slots 5..9 of row 0 invalid
        mask = np.ones((2, 32), bool)
        mask[0, 5:10] = False
        kv_mask = jnp.asarray(mask)
        want = reference_attention(q, k, v, causal=False,
                                   kv_positions_below=kv_below,
                                   kv_mask=kv_mask)
        got = _cached_flash_attention(q, k, v, False, kv_below,
                                      kv_mask, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_single_token_decode_parity(self):
        from deepspeedsyclsupport_tpu.models.layers import (
            _cached_flash_attention, reference_attention)

        q, k, v = self._data(sq=1)
        kv_below = jnp.asarray([[17], [9]], jnp.int32)
        want = reference_attention(q, k, v, causal=False,
                                   kv_positions_below=kv_below)
        got = _cached_flash_attention(q, k, v, False, kv_below, None,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestAlibiAndWindow:
    """ALiBi logit bias + sliding-window masking (BLOOM / Mistral support in
    the one kernel family; reference analogs: module_inject bloom container's
    alibi path, mistral sliding window in v2 model implementations)."""

    def test_alibi_parity(self):
        from deepspeedsyclsupport_tpu.models.layers import alibi_slopes

        q, k, v = _qkv(11, h=4, kvh=2)
        sl = jnp.asarray(alibi_slopes(4))
        ref = reference_attention(q, k, v, causal=True, alibi=sl)
        got = flash_attention(q, k, v, causal=True, alibi=sl, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_window_parity(self):
        q, k, v = _qkv(12)
        ref = reference_attention(q, k, v, causal=True, window=64)
        got = flash_attention(q, k, v, causal=True, window=64, interpret=True,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_alibi_window_grads(self):
        from deepspeedsyclsupport_tpu.models.layers import alibi_slopes

        q, k, v = _qkv(13, sq=128, d=32)
        sl = jnp.asarray(alibi_slopes(4))

        def f(fn):
            def loss(q, k, v):
                return (fn(q, k, v) ** 2).sum()
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        g_ref = f(lambda q, k, v: reference_attention(
            q, k, v, causal=True, alibi=sl, window=96))
        g_got = f(lambda q, k, v: flash_attention(
            q, k, v, causal=True, alibi=sl, window=96, interpret=True,
            block_q=128, block_k=128))
        for a, b in zip(g_got, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)

    def test_alibi_slopes_schedule(self):
        from deepspeedsyclsupport_tpu.models.layers import alibi_slopes

        s8 = alibi_slopes(8)
        np.testing.assert_allclose(s8, [2 ** (-i) for i in range(1, 9)],
                                   rtol=1e-6)
        s6 = alibi_slopes(6)           # non-power-of-2 interpolation
        assert s6.shape == (6,) and np.all(s6 > 0) and np.all(np.diff(s6[:4]) < 0)


# ------------------------------------------------------------ kinds of tile
def _segments(*lengths):
    return jnp.asarray(np.repeat(np.arange(len(lengths)), lengths)[None])


def _own_positions(sq, skv):
    return dict(q_positions=jnp.arange(sq, dtype=jnp.int32)[None] + skv - sq,
                kv_positions=jnp.arange(skv, dtype=jnp.int32)[None])


# every case holds a DEAD, an INTERIOR and a BOUNDARY tile at 128 x 128
# (``_tile_kinds`` checks that from the full mask), by another route each:
# name -> (sq, skv, q heads, kv heads, flash_attention / reference kwargs)
TILE_CASES = {
    "causal_3_blocks": (384, 384, 2, 2, {}),
    "offset_sq_lt_skv": (256, 512, 2, 2, {}),
    "length_not_a_block_multiple": (300, 300, 2, 2, {}),
    "window_bites": (640, 640, 2, 2, dict(window=300)),
    "window_cannot_bite": (384, 384, 2, 2, dict(window=4096)),
    # one segment ends on a block edge (128), the next inside a block (428)
    "segments_edge_and_inside": (640, 640, 2, 2,
                                 dict(segment_ids=_segments(128, 300, 212))),
    "custom_positions": (384, 384, 2, 2, _own_positions(384, 384)),
    "group_of_4_heads": (384, 384, 8, 2, {}),
    "alibi": (384, 384, 4, 4, dict(alibi=jnp.asarray([0.5, 0.25, 0.125,
                                                      0.0625]))),
}


def _tile_kinds(sq, skv, kw, block=128):
    """``{dead, interior, boundary}`` counts from the full validity mask."""
    qp = np.arange(sq)[:, None] + skv - sq
    kp = np.arange(skv)[None, :]
    ok = kp <= qp
    if kw.get("window") is not None:
        ok &= qp - kp < kw["window"]
    if "segment_ids" in kw:
        seg = np.asarray(kw["segment_ids"])[0]
        ok &= seg[:, None] == seg[None, :]
    pad = lambda n: -(-n // block) * block
    full = np.zeros((pad(sq), pad(skv)), bool)
    full[:sq, :skv] = ok
    kinds = dict(dead=0, interior=0, boundary=0)
    for i in range(0, full.shape[0], block):
        for j in range(0, full.shape[1], block):
            tile = full[i:i + block, j:j + block]
            kinds["dead" if not tile.any() else
                  "interior" if tile.all() else "boundary"] += 1
    return kinds


def _assert_forward_and_grads_match(attn, reference, q, k, v):
    """Forward at 2e-5 and dq / dk / dv at 2e-4: this file's tolerances."""
    def run(f):
        def loss(q, k, v):
            o = f(q, k, v)
            return jnp.sum(o * jnp.cos(o)), o
        # (ONE program a side: eagerly every op is one a shape)
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + g

    got, want = run(attn), run(reference)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", sorted(TILE_CASES))
def test_each_kind_of_tile_in_each_kernel(name):
    """Forward and dq / dk / dv against the XLA reference on shapes whose
    grids hold all three kinds of tile, so the unmasked body, the masked body
    and the skipped step of the forward, dQ and dK/dV kernels all run."""
    sq, skv, h, kvh, kw = TILE_CASES[name]
    kinds = _tile_kinds(sq, skv, kw)
    assert min(kinds.values()) > 0, kinds
    q, k, v = _qkv(20, b=1, sq=sq, skv=skv, h=h, kvh=kvh, d=32)

    _assert_forward_and_grads_match(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True,
                                        block_q=128, block_k=128, **kw),
        lambda q, k, v: reference_attention(q, k, v, causal=True, **kw),
        q, k, v)


@pytest.mark.parametrize("sq,skv,block,window", [
    (2048, 2048, 512, 4096),    # the training cells' shape: 6 + 4 live, 6 dead
    (640, 640, 128, 300),       # a window that bites
    (256, 512, 128, None),      # Sq != Skv
    (300, 300, 128, None),      # padded last blocks are boundary tiles
])
def test_tile_plan_counts_what_the_mask_holds(sq, skv, block, window):
    from deepspeedsyclsupport_tpu.ops.flash_attention import tile_plan

    plan = tile_plan(sq, skv, block, block, True, skv - sq, window)
    want = _tile_kinds(sq, skv, dict(window=window), block)
    assert plan == dict(want, live=want["interior"] + want["boundary"])
    if (sq, block) == (2048, 512):
        assert plan == dict(live=10, interior=6, boundary=4, dead=6)


def test_default_blocks_never_pad_more_than_a_512_block():
    from deepspeedsyclsupport_tpu.ops.flash_attention import _default_blocks

    for s in (1, 128, 200, 512, 1500, 1664, 2048, 4096, 5000, 8192):
        s_p, _, (bq, bk, cq, ck) = _default_blocks(s, s)
        assert s_p == -(-s // min(512, -(-s // 128) * 128)) \
            * min(512, -(-s // 128) * 128)
        assert s_p % bq == 0 and s_p % bk == 0
        assert bq % cq == 0 and bk % ck == 0 and cq % 128 == 0


@pytest.mark.parametrize("sq,skv,h,kvh,kw", [
    (512, 512, 4, 1, {}),
    (500, 500, 2, 2, dict(alibi=jnp.asarray([0.5, 0.125]), window=300)),
    (256, 512, 2, 2, {}),
], ids=["group_of_4_heads", "unaligned_alibi_window", "offset_sq_lt_skv"])
def test_a_grid_step_walked_as_several_compute_tiles(monkeypatch, sq, skv, h,
                                                     kvh, kw):
    """The rule's shape of a call: the block a grid step copies is larger
    than the tile it computes on, one rolled loop whose every tile takes the body its own kind asks for. A call with per-key rows
    (segments) keeps step and tile the same."""
    from deepspeedsyclsupport_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_COMPUTE_TILE", 128)
    monkeypatch.setattr(fa, "_PREFERRED_BLOCK", (256, 512))
    assert fa._default_blocks(512, 512)[2] == (256, 512, 128, 128)
    assert fa._default_blocks(512, 512, plain=False)[2] == (128,) * 4
    q, k, v = _qkv(21, b=1, sq=sq, skv=skv, h=h, kvh=kvh, d=32)

    _assert_forward_and_grads_match(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           interpret=True, **kw),
        lambda q, k, v: reference_attention(q, k, v, causal=True, **kw),
        q, k, v)
