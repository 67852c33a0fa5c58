"""Pallas paged decode attention: kernel-vs-reference parity (the CUDA-vs-
torch parity pattern of the reference's kernel tests, SURVEY.md §4), run in
interpret mode on the CPU sim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.ops.paged_attention import (
    paged_decode_attention, paged_decode_attention_reference)
from tests.unit.greedy import greedy


def _setup(rng, s=3, h=8, kvh=4, d=32, bs=16, bps=4, seq_lens=None):
    ks = jax.random.split(jax.random.PRNGKey(rng), 4)
    num_blocks = s * bps + 2
    q = jax.random.normal(ks[0], (s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (num_blocks * bs, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (num_blocks * bs, kvh, d), jnp.float32)
    # disjoint, shuffled block tables per sequence
    perm = np.asarray(jax.random.permutation(ks[3], num_blocks))
    tables = perm[:s * bps].reshape(s, bps).astype(np.int32)
    lens = np.asarray(seq_lens if seq_lens is not None
                      else [bs * bps, bs + 3, 1], np.int32)[:s]
    return q, k, v, jnp.asarray(tables), jnp.asarray(lens)


def _in_pool(cache, layer, num_layers=3):
    """``cache`` [slots, KVH, D] as layer ``layer`` of a whole pool
    [L, slots, KVH, D] whose other layers hold other noise (the serving
    forwards hand the kernels the pool and the layer, never a slice)."""
    noise = jax.random.normal(jax.random.PRNGKey(11),
                              (num_layers,) + cache.shape, cache.dtype)
    return noise.at[layer].set(cache)


# None: one layer's 3-D cache, as before; 0 and L-1: the same cache read as
# that layer of a 4-D pool — the kernel entries tell the two apart by rank
POOL_LAYERS = [None, 0, 2]
ARCH_KW = {"plain": {}, "alibi": {"alibi": True}, "window": {"window": 6},
           "alibi_window": {"alibi": True, "window": 9}}


def _arch_kw(name, h):
    from deepspeedsyclsupport_tpu.models.layers import alibi_slopes

    kw = dict(ARCH_KW[name])
    if kw.pop("alibi", False):
        kw["alibi"] = jnp.asarray(alibi_slopes(h))
    return kw


def _assert_pool_layer_parity(call, k, v, layer, ref):
    """``call(k, v, **kw)`` runs the kernel; with ``layer`` it must return,
    from the pool, exactly what it returns from the layer's own cache."""
    flat = call(k, v)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    if layer is not None:
        k_pool, v_pool = _in_pool(k, layer), _in_pool(v, layer)
        pooled = call(k_pool, v_pool, layer=layer)
        np.testing.assert_array_equal(np.asarray(pooled), np.asarray(flat))
        # a traced layer, as inside the forwards' layer loop
        traced = jax.jit(lambda kp, vp, l: call(kp, vp, layer=l))(
            k_pool, v_pool, jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(traced), np.asarray(flat))


class TestPagedDecodeParity:
    @pytest.mark.parametrize("layer", POOL_LAYERS)
    @pytest.mark.parametrize("arch", ["plain", "alibi_window"])
    @pytest.mark.parametrize("seq_lens", [[64, 19, 1], [5, 5, 5], [64, 64, 64]])
    def test_kernel_matches_reference(self, seq_lens, arch, layer):
        q, k, v, tables, lens = _setup(0, seq_lens=seq_lens)   # GQA 8 over 4
        kw = _arch_kw(arch, q.shape[1])
        ref = paged_decode_attention_reference(q, k, v, tables, lens,
                                               block_size=16, **kw)
        if layer is not None:   # the oracle slices pool[layer] at its seam
            np.testing.assert_array_equal(
                np.asarray(paged_decode_attention_reference(
                    q, _in_pool(k, layer), _in_pool(v, layer), tables, lens,
                    block_size=16, layer=layer, **kw)), np.asarray(ref))
        _assert_pool_layer_parity(
            lambda kc, vc, **at: paged_decode_attention(
                q, kc, vc, tables, lens, block_size=16,
                impl="pallas_interpret", **kw, **at), k, v, layer, ref)

    def test_mha_no_gqa(self):
        q, k, v, tables, lens = _setup(1, h=4, kvh=4)
        ref = paged_decode_attention_reference(q, k, v, tables, lens,
                                               block_size=16)
        got = paged_decode_attention(q, k, v, tables, lens, block_size=16,
                                     impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_reference_matches_dense(self):
        """The paged reference itself must equal dense attention over the
        logically-contiguous KV."""
        q, k, v, tables, lens = _setup(2, s=2, seq_lens=[40, 7])
        got = paged_decode_attention_reference(q, k, v, tables, lens,
                                               block_size=16)
        for i in range(2):
            # materialize sequence i's KV in logical order
            idx = []
            for b in np.asarray(tables[i]):
                idx.extend(range(b * 16, (b + 1) * 16))
            idx = np.asarray(idx)[:int(lens[i])]
            ki = np.repeat(np.asarray(k)[idx], 2, axis=1)  # GQA expand
            vi = np.repeat(np.asarray(v)[idx], 2, axis=1)
            logits = np.einsum("hd,thd->ht", np.asarray(q[i]), ki) / np.sqrt(32)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want = np.einsum("ht,thd->hd", p, vi)
            np.testing.assert_allclose(np.asarray(got[i]), want, rtol=2e-5,
                                       atol=2e-5)

    def test_bf16_inputs(self):
        q, k, v, tables, lens = _setup(3)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = paged_decode_attention_reference(qb, kb, vb, tables, lens,
                                               block_size=16)
        got = paged_decode_attention(qb, kb, vb, tables, lens, block_size=16,
                                     impl="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)


class TestRaggedPrefillKernel:
    """Atom-based ragged paged prefill attention (the arXiv:2604.15464 /
    reference blocked_flash+atom_builder unification): kernel vs exact
    reference, and the full engine path through atoms."""

    def _setup(self, seed=0, bs=8, bps=6, kvh=2, h=4, d=32, bq=16, A=4):
        rng = np.random.RandomState(seed)
        num_slots = 96
        k_cache = jnp.asarray(rng.randn(num_slots, kvh, d), jnp.float32)
        v_cache = jnp.asarray(rng.randn(num_slots, kvh, d), jnp.float32)
        q = jnp.asarray(rng.randn(A, bq, h, d), jnp.float32)
        tables = jnp.asarray(rng.randint(0, num_slots // bs, (A, bps)),
                             jnp.int32)
        pos0 = jnp.asarray([0, 13, 5, 40], jnp.int32)
        qlen = jnp.asarray([bq, 9, 0, 7], jnp.int32)  # full/partial/dead
        return q, k_cache, v_cache, tables, pos0, qlen, bs

    def test_kernel_matches_reference(self):
        from deepspeedsyclsupport_tpu.ops.paged_attention import (
            ragged_prefill_attention_pallas,
            ragged_prefill_attention_reference)

        q, k, v, tables, pos0, qlen, bs = self._setup()
        ref = ragged_prefill_attention_reference(q, k, v, tables, pos0,
                                                 qlen, block_size=bs)
        got = ragged_prefill_attention_pallas(q, k, v, tables, pos0, qlen,
                                              block_size=bs, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_and_single_block(self):
        from deepspeedsyclsupport_tpu.ops.paged_attention import (
            ragged_prefill_attention_pallas,
            ragged_prefill_attention_reference)

        q, k, v, tables, pos0, qlen, bs = self._setup(seed=3, kvh=1, h=4,
                                                      bps=1, bq=8)
        ref = ragged_prefill_attention_reference(q, k, v, tables, pos0,
                                                 jnp.minimum(qlen, 8),
                                                 block_size=bs)
        got = ragged_prefill_attention_pallas(q, k, v, tables, pos0,
                                              jnp.minimum(qlen, 8),
                                              block_size=bs, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestEngineKernelPath:
    """Engine serving through the atom kernel end-to-end (interpret mode)."""

    def _engine(self, **kw):
        from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
        from deepspeedsyclsupport_tpu.models import build_model

        model = build_model("tiny", dtype="float32")
        params = model.init_params()
        kw.setdefault("dtype", jnp.float32)
        kw.setdefault("block_size", 8)
        kw.setdefault("max_context", 64)
        kw.setdefault("max_tokens_per_batch", 16)
        kw.setdefault("max_sequences", 4)
        kw.setdefault("prefill_attn", "kernel_interpret")
        kw.setdefault("atom_q_size", 8)
        return model, params, InferenceEngineV2(model, params, **kw)

    def test_prefill_logits_match_dense(self):
        model, params, eng = self._engine()
        prompt = [1, 5, 9, 200, 3]
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)

    def test_split_prompt_and_generate(self):
        model, params, eng = self._engine()
        prompt = list(np.random.RandomState(0).randint(1, 500, size=20))
        out = eng.put([1], [prompt])  # split across forwards by the budget
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)
        eng.flush([1])
        # greedy generate (mixed prefill + decode fast path)
        got = eng.generate([[7, 3, 11], [4, 100, 42, 8, 19]],
                           max_new_tokens=5)
        for p, g in zip([[7, 3, 11], [4, 100, 42, 8, 19]], got):
            assert g == greedy(model, params, p, 5)


    @pytest.mark.parametrize("newcomer", [[4, 100, 42, 8, 19, 77, 5, 3, 61],
                                          [250]],
                             ids=["two_atom_prompt", "one_token_prompt"])
    def test_mixed_round_matches_dense(self, newcomer):
        """Two sequences decode (one-row tiles) in the forward that takes a
        newcomer's prompt (atoms, or a third one-row tile): every row's
        logits are the dense model's over the whole sequence."""
        model, params, eng = self._engine()
        seqs = {1: [7, 3, 11], 2: [9, 200, 31, 5, 88, 2, 14, 6, 90, 120]}
        eng.put([1, 2], [seqs[1], seqs[2]])
        step = {1: [33], 2: [64], 3: newcomer}
        forwards = eng._tick
        out = eng.put([1, 2, 3], [step[1], step[2], step[3]])
        assert eng._tick == forwards + 1       # ONE mixed forward
        seqs[3] = []
        for uid in (1, 2, 3):
            seq = seqs[uid] + step[uid]
            dense = model.apply(params, jnp.asarray([seq], jnp.int32))
            np.testing.assert_allclose(out[uid], np.asarray(dense[0, -1]),
                                       rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layer", POOL_LAYERS)
@pytest.mark.parametrize("arch", ["alibi", "window", "alibi_window"])
def test_ragged_prefill_alibi_window_parity(arch, layer):
    """ALiBi + sliding window through the atom kernel (bloom/mistral TTFT
    stays on the fast path), GQA 4 over 2; from one layer's cache and from
    the whole pool."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        ragged_prefill_attention_pallas, ragged_prefill_attention_reference)

    rng = np.random.RandomState(7)
    bs, bps, kvh, h, d, bq, A = 8, 6, 2, 4, 32, 16, 3
    k_cache = jnp.asarray(rng.randn(64, kvh, d), jnp.float32)
    v_cache = jnp.asarray(rng.randn(64, kvh, d), jnp.float32)
    q = jnp.asarray(rng.randn(A, bq, h, d), jnp.float32)
    tables = jnp.asarray(rng.randint(0, 8, (A, bps)), jnp.int32)
    pos0 = jnp.asarray([0, 13, 5], jnp.int32)
    qlen = jnp.asarray([16, 9, 4], jnp.int32)
    kw = _arch_kw(arch, h)
    ref = ragged_prefill_attention_reference(
        q, k_cache, v_cache, tables, pos0, qlen, block_size=bs, **kw)
    _assert_pool_layer_parity(
        lambda kc, vc, **at: ragged_prefill_attention_pallas(
            q, kc, vc, tables, pos0, qlen, block_size=bs, interpret=True,
            **kw, **at), k_cache, v_cache, layer, ref)


def test_engine_kernel_path_alibi_and_window():
    """Arch-zoo serving through the atom kernel: bloom-style alibi and a
    sliding-window config both produce greedy parity with the dense model."""
    import dataclasses

    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    for kw in (dict(pos_embed="alibi"), dict(sliding_window=4)):
        cfg = dataclasses.replace(get_config("tiny"), dtype="float32", **kw)
        model = build_model(cfg)
        params = model.init_params()
        eng = InferenceEngineV2(model, params, dtype=jnp.float32,
                                block_size=8, max_context=64,
                                max_tokens_per_batch=16, max_sequences=4,
                                prefill_attn="kernel_interpret",
                                atom_q_size=8)
        prompts = [[7, 3, 11, 8, 2, 90]]
        got = eng.generate(prompts, max_new_tokens=4)
        assert got[0] == greedy(model, params, prompts[0], 4)


def test_decode_dead_slot_exact_zero():
    """seq_len == 0 slots must produce exact zeros from BOTH the unified
    kernel and the jnp oracle (regression: the oracle used to emit
    uniform-softmax garbage for dead slots)."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_attention_reference)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(4, 4, 32), jnp.float32)
    kc = jnp.asarray(rng.randn(64, 2, 32), jnp.float32)
    vc = jnp.asarray(rng.randn(64, 2, 32), jnp.float32)
    bt = jnp.asarray(rng.randint(0, 8, (4, 4)), jnp.int32)
    sl = jnp.asarray([17, 1, 0, 30], jnp.int32)
    ref = paged_decode_attention_reference(q, kc, vc, bt, sl, block_size=8)
    got = paged_decode_attention(q, kc, vc, bt, sl, block_size=8,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(got[2]).max()) == 0.0


@pytest.mark.parametrize("pages", [1, 2])
def test_the_kernel_body_is_traced_once_a_tile_not_once_a_call(monkeypatch,
                                                               pages):
    """A forward calls the one body for each layer stack, ``decode_forward``
    again, every further static shape of ``ragged_forward`` again: calls of
    the same shapes and choices share one trace of it, whoever traces them;
    a choice made differently (the KV blocks a loop step takes) is another
    trace, and the program is the one a plain call gives."""
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    traced = []
    body = pa._prefill_kernel

    def counting(*refs, **kw):
        traced.append(kw["pages"])
        return body(*refs, **kw)

    monkeypatch.setattr(pa, "_prefill_kernel", counting)
    monkeypatch.setattr(pa, "_kv_pages_per_step", lambda *a: pages)
    # a tile no other test of this process asks for: 5 slots, 6 heads
    q, k, v, tables, lens = _setup(3, s=5, h=6, kvh=3, d=16, bps=3,
                                   seq_lens=[48, 19, 1, 0, 7])
    k, v = _in_pool(k, 1), _in_pool(v, 1)

    def stack(q, layer):
        return pa.paged_decode_attention_pallas(
            q, k, v, tables, lens, block_size=16, layer=layer,
            interpret=True)

    def forward(q):       # two layer stacks, as a model of two kinds has
        return stack(q, jnp.int32(1)) + stack(q * 2.0, jnp.int32(1))

    jax.jit(forward).lower(q)
    assert traced == [pages]
    out = jax.jit(lambda q: stack(q, jnp.int32(1)))(q)      # another program
    assert traced == [pages]
    ref = paged_decode_attention_reference(q, k, v, tables, lens,
                                           block_size=16, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------- a K-and-V pool under a tile of several rows
# (pos0, qlen) of each atom over a table of 11 blocks of 128 keys (1,408):
KV_TILES = [
    (300, 8),     # 308 keys: a whole step at no width
    (0, 0),       # a dead atom: zero steps, zeros
    (1404, 8),    # longer than its table: what the table holds
    (1030, 5),    # its first row stands inside the last step at any width
    (600, 8),
]


def _kv_tile_selection(rng, bq, keys):
    """int8 [atoms, bq, keys]: 3 keys in 10 of what a row may see, and the
    rows a step's mask must not fool: atom 3's row 0 selects nothing under
    key 1,024 (every step under its last holds no selected key: ``m`` stays
    at its first value there and no ``exp`` may read 1), its row 1 only
    keys 5-20 (all in the first step), its row 2 nothing at all."""
    pos0 = np.asarray([p for p, _ in KV_TILES])
    seen = np.arange(keys)[None, None, :] <= (
        pos0[:, None, None] + np.arange(bq)[None, :, None])
    sel = np.logical_and(rng.random((len(KV_TILES), bq, keys)) < 0.3, seen)
    sel[3, 0, :1024] = False
    sel[3, 0, 1024:1031] = True
    sel[3, 1] = False
    sel[3, 1, 5:21] = True
    sel[3, 2] = False
    return jnp.asarray(sel, jnp.int8)


def _kv_tile_blocks(rng, bq, kvh, bps):
    """int8 [atoms, kvh, blocks, bq], a selection of BLOCKS a kv head: half
    of the table's blocks a (head, row), each head its own, and the rows a
    step's widening must not fool: atom 3's row 0 selects its own block
    alone (the last step's; nothing under it), its row 1 block 0 alone,
    its row 2 nothing; atom 4's rows select DISJOINT blocks (row r block r
    mod 4 and no other, under either head), so a flag that reached a
    neighbouring row, block or head shows."""
    sel = rng.random((len(KV_TILES), kvh, bps, bq)) < 0.5
    sel[3, :, :, :3] = False
    sel[3, :, 1030 // 128, 0] = True
    sel[3, :, 0, 1] = True
    sel[4] = np.arange(bps)[:, None] == np.arange(bq)[None, :] % 4
    return jnp.asarray(sel, jnp.int8)


@pytest.mark.parametrize("arch", ["plain", "alibi_window"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("masked", [False, True, "blocks"],
                         ids=["dense", "selected", "blocks"])
@pytest.mark.parametrize("pages", [1, 2, 4, 8])
def test_a_k_and_v_tile_of_several_rows_is_its_twin_at_any_step(
        monkeypatch, pages, masked, group, arch):
    """A K-and-V pool's tile of several rows walks steps of ``pages``
    blocks, one kv head's scores at a time; under an indexer's selection a
    pair counts only where the mask is nonzero, and under a selection of
    BLOCKS a kv head (two heads here; a step's blocks widened to its keys
    inside the kernel) only where the row's own head chose the key's block:
    a context that is no multiple of the step, a dead atom, rows of
    disjoint blocks, and at 4 and 8 blocks a step a last step past the
    table's end (11 blocks). Blocks OUTSIDE the tables'
    live part are NaN (another sequence's, or never written): a wide
    step's blocks past the context's end, or below the window's start,
    must not be read as they lie."""
    from deepspeedsyclsupport_tpu.models.layers import alibi_slopes
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_kv_pages_per_step", lambda *a: pages)
    bs, bps, blocks, bq, kvh, d = 128, 11, 60, 8, 2, 16
    h = kvh * group
    rng = np.random.default_rng(pages + 10 * group + bool(masked)
                                + 100 * (masked == "blocks"))
    window = 200 if arch == "alibi_window" else None
    pos0, qlen = (np.asarray(x) for x in zip(*KV_TILES))
    hi = np.minimum(pos0 + qlen, bps * bs)
    n = len(KV_TILES)
    tables = rng.permutation(blocks)[:n * bps].reshape(n, bps)
    lo = np.zeros(n, int) if window is None else \
        np.maximum(pos0 + 1 - window, 0) // bs
    pool = np.full((2, 2, blocks * bs, kvh, d), np.nan, np.float32)
    for i in range(n):
        for blk in tables[i, lo[i]:-(-hi[i] // bs)]:
            pool[:, :, blk * bs:(blk + 1) * bs] = rng.standard_normal(
                (2, 2, bs, kvh, d))
    q = jnp.asarray(rng.standard_normal((n, bq, h, d)) * 0.5, jnp.float32)
    kw = dict(block_size=bs, layer=jnp.int32(1), window=window)
    if arch == "alibi_window":
        kw["alibi"] = jnp.asarray(alibi_slopes(h))
    if masked == "blocks":
        kw["sel"] = _kv_tile_blocks(rng, bq, kvh, bps)
    elif masked:
        kw["sel"] = _kv_tile_selection(rng, bq, bps * bs)
    args = (jnp.asarray(tables, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(qlen, jnp.int32))
    got = np.asarray(pa.ragged_prefill_attention_pallas(
        q, jnp.asarray(pool[0]), jnp.asarray(pool[1]), *args,
        interpret=True, **kw))
    clean = jnp.asarray(np.nan_to_num(pool))
    want = np.asarray(pa.ragged_prefill_attention_reference(
        q, clean[0], clean[1], *args, **kw))
    rows = np.arange(bq)[None, :] < qlen[:, None]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[rows], want[rows], atol=2e-5, rtol=2e-5)
    assert not got[~rows].any()              # dead rows and tiles: zeros
    if masked and window is None:
        assert not got[3, 2].any()           # a row that selected nothing
        assert np.abs(got[3, :2]).max() > 1e-3
    if masked == "blocks" and window is None:
        # the twin itself, against blocks read off by hand: atom 4's row r
        # attends to block r mod 4 alone (all of it under the row)
        k, v = np.asarray(clean[0][1]), np.asarray(clean[1][1])
        for r in (0, 1, 6):
            slots = tables[4, r % 4] * bs + np.arange(bs)
            for head in range(h):
                g = head // group
                logit = k[slots, g] @ np.asarray(q[4, r, head]) / np.sqrt(d)
                w = np.exp(logit - logit.max())
                np.testing.assert_allclose(
                    got[4, r, head], (w / w.sum()) @ v[slots, g],
                    atol=2e-5, rtol=2e-5)


# --------------------------------------- the hand-over between a call's tiles
# A tile's last step starts the first KV step of the grid's NEXT tile where
# both walk a step. Each case: the pool, the tile's rows, (pos0, qlen) of the
# call's tiles in grid order, and what else the tile runs under.
def _rows_of(lens):
    """(pos0, qlen) of one-row tiles at these sequence lengths (0: dead)."""
    return [(max(n - 1, 0), int(n > 0)) for n in lens]


HAND_OVER = {
    # one-row tiles, K and V: dead slots between live ones, at the ends
    "live_dead_live": dict(bq=1, tiles=_rows_of([40, 0, 17, 0, 0, 96, 1, 0])),
    # a one-block context behind a long one and before one: the slot a
    # tile's first step lies in no longer follows the step's parity
    "one_block_between_long": dict(
        bq=1, tiles=_rows_of([96, 5, 81, 16, 1, 33, 96])),
    "the_first_tile_alone": dict(bq=1, tiles=_rows_of([70, 0, 0, 0])),
    "the_first_tile_dead": dict(bq=1, tiles=_rows_of([0, 0, 50, 3])),
    # a window that skips whole blocks: lo_blk 3, 0, 2, 4 (odd and even)
    "window_one_row": dict(bq=1, window=20,
                           tiles=_rows_of([80, 12, 0, 60, 96, 17])),
    "window_atoms": dict(bq=8, window=20, pages=2,
                         tiles=[(60, 8), (0, 5), (0, 0), (88, 8), (40, 3)]),
    # a context longer than its table beside a short one: what the table
    # holds, and a copy started for no step past it
    "past_the_table": dict(bq=8, tiles=[(92, 8), (3, 8), (90, 6)]),
    "atoms_of_128_rows": dict(bq=128, bs=64, bps=4, h=2, kvh=1, tiles=[
        (0, 128), (128, 77), (0, 0), (64, 128), (0, 3)]),
    "masked_atoms": dict(bq=8, bs=64, bps=4, masked=True, tiles=[
        (100, 8), (0, 0), (30, 8), (200, 5), (0, 2)]),
    # a latent pool under a head-tile axis (128 heads in four tiles): an
    # atom's head tiles hand over among themselves and to the next atom; a
    # dead tile at pos0 > 0 walks its loop with every row masked
    "latent_head_tiles": dict(bq=8, latent=True, h=128, pages=2, tiles=[
        (30, 8), (17, 0), (0, 0), (70, 4), (0, 8)]),
    "latent_one_row": dict(bq=1, latent=True, h=4, pages=2,
                           tiles=_rows_of([50, 0, 96, 1, 20])),
}


@pytest.mark.parametrize("case", list(HAND_OVER))
def test_a_tile_hands_its_successor_the_first_kv_step(monkeypatch, case):
    """The kernel (interpreted) against the reference at the tolerances the
    other parity tests hold, on grids where tiles that walk and tiles that
    do not follow one another in every order. Blocks outside the tables'
    live part are NaN: a copy made for the wrong tile, step or slot reads
    one."""
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    c = dict(bs=16, bps=6, h=4, kvh=2, d=16, window=None, pages=None,
             masked=False, latent=False)
    c.update(HAND_OVER[case])
    bq, bs, bps, h, d = c["bq"], c["bs"], c["bps"], c["h"], c["d"]
    if c["pages"]:
        monkeypatch.setattr(pa, "_kv_pages_per_step",
                            lambda *a: c["pages"])
    if c["latent"] and h > 32:           # four head tiles at these widths
        monkeypatch.setattr(pa, "_HEAD_TILE_BUDGET", pa._ragged_vmem_need(
            bq, h // 4, 1, d, bs, 4))
    pos0, qlen = (np.asarray(x) for x in zip(*c["tiles"]))
    n = len(pos0)
    rng = np.random.default_rng(len(case))
    blocks = n * bps + 3
    tables = rng.permutation(blocks)[:n * bps].reshape(n, bps)
    row = (d,) if c["latent"] else (c["kvh"], d)
    pool = np.full((2, 2, blocks * bs) + row, np.nan, np.float32)
    hi = np.minimum(pos0 + qlen, bps * bs)
    lo = np.zeros(n, int) if c["window"] is None else \
        np.maximum(pos0 + 1 - c["window"], 0) // bs
    for i in range(n):
        for blk in tables[i, lo[i]:-(-hi[i] // bs)]:
            pool[:, :, blk * bs:(blk + 1) * bs] = rng.standard_normal(
                (2, 2, bs) + row)
    q = jnp.asarray(rng.standard_normal((n, bq, h, d)) * 0.5, jnp.float32)
    kw = dict(block_size=bs, layer=jnp.int32(1), window=c["window"])
    if c["latent"]:
        kw["v_dim"] = d // 2
    if c["masked"]:
        seen = np.arange(bps * bs)[None, None, :] <= (
            pos0[:, None, None] + np.arange(bq)[None, :, None])
        kw["sel"] = jnp.asarray(np.logical_and(
            rng.random((n, bq, bps * bs)) < 0.4, seen), jnp.int8)
    args = (jnp.asarray(tables, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(qlen, jnp.int32))
    pools = (pool[0], None) if c["latent"] else (pool[0], pool[1])
    got = np.asarray(pa.ragged_prefill_attention_pallas(
        q, *(p if p is None else jnp.asarray(p) for p in pools), *args,
        interpret=True, **kw))
    want = np.asarray(pa.ragged_prefill_attention_reference(
        q, *(p if p is None else jnp.asarray(np.nan_to_num(p))
             for p in pools), *args, **kw))
    rows = np.arange(bq)[None, :] < qlen[:, None]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[rows], want[rows], atol=2e-5, rtol=2e-5)
    assert not got[~rows].any()              # dead rows and tiles: zeros


def _walk_the_grid(pos0, qlen, head_tiles, **shape):
    """The starts and the waits of one call as ``_attend_tile`` makes them,
    tile by tile in grid order, with ``tile_span`` as the kernel has it;
    two slots, one semaphore each. Returns ``(warm, handed)``: the tiles
    that waited for a first step they did not start, and those that
    started their successor's."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import tile_span

    spans = [tile_span(pos0[a], qlen[a], xp=np, **shape)
             for a in range(len(pos0)) for _ in range(head_tiles)]
    step_keys = shape["pages"] * shape["block_size"]
    in_flight = {}                       # slot -> (tile, step) on its way
    carried = None                       # the SMEM scalar
    warm, handed = [], []

    def start(slot, what):
        assert slot not in in_flight, f"{what} started over {in_flight}"
        in_flight[slot] = what

    for i, (_lo_blk, lo_step, kv_hi, walks) in enumerate(spans):
        if not walks:
            continue
        after = spans[i + 1] if i + 1 < len(spans) else None
        hands = after is not None and bool(after[3])
        if i > 0 and spans[i - 1][3]:
            warm.append(i)
            first = carried
        else:
            first = 0
            start(first, (i, lo_step))
        n_steps = -(-kv_hi // step_keys)
        for j in range(lo_step, n_steps):
            cur = (first + j - lo_step) % 2
            if j + 1 < n_steps:
                start(1 - cur, (i, j + 1))
            elif hands:
                start(1 - cur, (i + 1, after[1]))
                handed.append(i)
                carried = 1 - cur
            # awaited once, on the semaphore it was started on
            assert in_flight.pop(cur, None) == (i, j)
    assert not in_flight                 # started and never awaited
    return warm, handed


@pytest.mark.parametrize("seed", range(8))
def test_every_hand_over_has_the_successor_that_waits_and_no_other(seed):
    """Random batches through the pairing alone: whatever the contexts, the
    dead tiles, the window and the blocks a step, every copy is started
    once and awaited once on its slot's semaphore, a tile hands over iff
    the next one starts warm, and the host's count is the kernel's."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (tile_span,
                                                              warm_tiles)

    rng = np.random.default_rng(seed)
    for _ in range(40):
        shape = dict(block_size=int(rng.choice([4, 16])),
                     max_blocks=int(rng.integers(1, 9)),
                     pages=int(rng.choice([1, 2, 8])),
                     window=[None, 1, 7, 40][rng.integers(4)],
                     latent=bool(rng.integers(2)))
        head_tiles = int(rng.choice([1, 1, 4])) if shape["latent"] else 1
        n = int(rng.integers(1, 12))
        cap = shape["max_blocks"] * shape["block_size"]
        pos0 = rng.integers(0, cap + 9, n)          # some past the table
        qlen = rng.integers(0, 9, n) * rng.integers(0, 2, n)
        pos0[rng.integers(0, 2, n) * (qlen == 0) > 0] = 0
        warm, handed = _walk_the_grid(pos0, qlen, head_tiles, **shape)
        assert warm == [i + 1 for i in handed]
        walks = np.repeat(tile_span(pos0, qlen, xp=np, **shape)[3],
                          head_tiles)
        assert len(warm) == warm_tiles(walks)
        assert set(warm) <= set(np.flatnonzero(walks))
