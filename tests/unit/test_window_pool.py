"""The windowed layers' pool of a stack of two attention kinds
(``ModelConfig.attn_period``), on the host and under the interpreted kernel:
a sequence never holds more than its bound, a freed table entry is never
dereferenced, admission takes a block of each pool or of neither, and the
counts the ``round`` record carries against counts by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2.config import RaggedInferenceConfig
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (
    window_blocks_a_sequence)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (
    BlockedAllocator, SequenceDescriptor, WindowedAllocator, attention_work,
    build_ragged_batch, window_work)
from deepspeedsyclsupport_tpu.inference.v2.scheduler import _admit
from deepspeedsyclsupport_tpu.ops.paged_attention import (
    paged_decode_attention, ragged_prefill_attention, tile_span)


def grow(window, chunk, bs, total, allocator, d, lengths=None):
    """``d`` grown to ``total`` tokens a chunk at a time, as ``put()`` does
    it: blocks of both pools at admission, the forward, then what is out of
    the window given back. Yields the blocks held at the forward."""
    rng = np.random.default_rng(total)
    while d.n_cached < total:
        n = min(total - d.n_cached,
                chunk if lengths is None else int(rng.integers(1, chunk + 1)))
        d.pending = [1] * n
        assert _admit(d, n, allocator, bs, total)
        yield len(d.window_held)
        d.pending, d.n_cached = [], d.n_cached + n
        allocator.window.release(d.out_of_window(window, bs))


# (window, longest chunk, block): whole blocks and not
SHAPES = [(4096, 768, 64), (16, 16, 8), (20, 13, 8), (100, 7, 16),
          (64, 200, 32), (8, 1, 8)]


@pytest.mark.parametrize("ragged", [False, True], ids=["whole", "ragged"])
@pytest.mark.parametrize("window,chunk,bs", SHAPES)
def test_a_sequence_grown_to_40_windows_stays_within_its_bound(
        window, chunk, bs, ragged):
    cfg = RaggedInferenceConfig(block_size=bs, max_tokens_per_batch=chunk,
                                max_sequences=2,
                                max_context=-(-41 * window // bs) * bs)
    bound = window_blocks_a_sequence(window, cfg)
    assert bound == (window + chunk + 2 * bs - 3) // bs
    if window % bs == 0 and chunk % bs == 0:
        assert bound * bs == window + chunk + bs
    total = 40 * window
    alloc = WindowedAllocator(BlockedAllocator(-(-total // bs)),
                              BlockedAllocator(bound))     # ONE sequence's
    d = SequenceDescriptor(uid=1, window_blocks=[])
    held = list(grow(window, chunk, bs, total, alloc, d,
                     lengths="ragged" if ragged else None))
    assert max(held) <= bound
    # ... and is no looser than a block: chunks that start on a block's
    # edge stay one under it, one that starts mid-block reaches it
    assert max(held) >= bound - 1
    # the full pool kept every block, the table keeps logical positions
    assert len(d.blocks) == len(d.window_blocks) == -(-total // bs)
    assert d.window_freed == (total - window + 1) // bs
    assert set(d.window_blocks[:d.window_freed]) <= {0}
    alloc.window.release(d.window_held)
    alloc.full.release(d.blocks)
    assert alloc.free_blocks == alloc.num_blocks


def test_admission_takes_a_block_of_each_pool_or_of_neither(monkeypatch):
    full, window = BlockedAllocator(8), BlockedAllocator(2)
    alloc = WindowedAllocator(full, window)
    assert (alloc.num_blocks, alloc.free_blocks) == (10, 10)
    d = SequenceDescriptor(uid=1, window_blocks=[], pending=[1] * 24)
    assert not _admit(d, 24, alloc, 8, 64)           # 3 blocks, window has 2
    assert (full.free_blocks, window.free_blocks, d.blocks) == (8, 2, [])
    assert _admit(d, 16, alloc, 8, 64)
    assert (len(d.blocks), len(d.window_blocks), alloc.free_blocks) \
        == (2, 2, 6)
    # an injected failure of the second allocation gives the first back
    window.release(d.window_blocks)
    d.window_blocks, d.blocks = [], []
    full.release([0, 1])
    monkeypatch.setattr(window, "try_allocate", lambda n: None)
    assert not _admit(d, 8, alloc, 8, 64)
    assert (full.free_blocks, d.blocks, d.window_blocks) == (8, [], [])
    # a model with one pool: the plain allocator, as ever
    plain = SequenceDescriptor(uid=2, pending=[1] * 8)
    assert _admit(plain, 8, full, 8, 64) and plain.window_blocks is None


def test_the_batch_carries_both_tables():
    d = SequenceDescriptor(uid=1, n_cached=40, pending=[5] * 12,
                           blocks=[9, 8, 7, 6, 5, 4, 3],
                           window_blocks=[0, 0, 0, 2, 1, 3, 4],
                           window_freed=3)
    e = SequenceDescriptor(uid=2, n_cached=3, pending=[6], blocks=[1],
                           window_blocks=[5])
    batch = build_ragged_batch([(d, 12), (e, 1)], 16, 4, 8, atom_q=8)
    assert batch.window_tables[0].tolist() == [0, 0, 0, 2, 1, 3, 4, 0]
    assert batch.window_tables[1].tolist() == [5] + [0] * 7
    assert batch.block_tables[0].tolist() == [9, 8, 7, 6, 5, 4, 3, 0]
    live = batch.atom_qlen > 0
    assert live.sum() == 2
    assert (batch.atom_window_tables[live] == batch.window_tables[0]).all()
    assert (batch.atom_tables[live] == batch.block_tables[0]).all()
    assert len(batch.window_args) == 2
    # ... and a model with one pool carries neither
    plain = build_ragged_batch(
        [(SequenceDescriptor(uid=3, pending=[1, 2], blocks=[0]), 2)],
        16, 4, 8, atom_q=8)
    assert plain.window_tables is None and plain.window_args == ()


def test_the_windows_work_against_a_count_by_hand():
    """A chunk of 20 rows at position 30 under a window of 16 and atoms of 8,
    and one of 10 rows at position 0 (its first rows see fewer keys than the
    window has)."""
    descs = [SequenceDescriptor(uid=1, n_cached=30),
             SequenceDescriptor(uid=2, n_cached=0),
             SequenceDescriptor(uid=3, n_cached=50)]      # a decode row
    pairs, swa_keys, full_keys = window_work(descs, [20, 10, 1], 16, 8)
    by_hand = sum(min(p + 1, 16) for p in range(30, 50)) \
        + sum(min(p + 1, 16) for p in range(10))
    assert pairs == by_hand == 20 * 16 + 55
    # atoms [30, 38), [38, 46), [46, 50) and [0, 8), [8, 10)
    assert swa_keys == (38 - 15) + (46 - 23) + (50 - 31) + 8 + 10
    assert full_keys == 38 + 46 + 50 + 8 + 10
    assert pairs <= attention_work(descs, [20, 10, 1], 8)[0]
    # a window no chunk reaches: the windowed layer is a full one
    assert window_work(descs, [20, 10, 1], 1000, 8) == (
        attention_work(descs, [20, 10, 1], 8)[0], full_keys, full_keys)


# -------------------------------------- a freed entry is never dereferenced
def poisoned(window, bs, ctx, kvh=2, d=16, seed=0):
    """One sequence of ``ctx`` cached tokens whose window pool has NaNs in
    EVERY block it does not hold (block 0, where its freed entries point,
    among them), and a clean copy with every block of the context in place
    for the oracle. -> (pools, table, clean pools, clean table)."""
    blocks = -(-(ctx + 1) // bs)          # the next token's block with them
    d_seq = SequenceDescriptor(uid=1, n_cached=ctx, window_blocks=list(
        range(1, blocks + 1)))
    d_seq.out_of_window(window, bs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    clean = [jax.random.normal(k, (1, (blocks + 1) * bs, kvh, d))
             for k in ks]
    held = np.zeros((blocks + 1) * bs, bool)
    for b in d_seq.window_held:
        held[b * bs:(b + 1) * bs] = True
    pools = [jnp.where(held[None, :, None, None], p, jnp.nan) for p in clean]
    bps = blocks + 2
    table = np.zeros((1, bps), np.int32)
    table[0, :blocks] = d_seq.window_blocks
    whole = np.zeros((1, bps), np.int32)
    whole[0, :blocks] = np.arange(1, blocks + 1)
    assert d_seq.window_freed > 2 and not held[:bs].any()
    return pools, table, clean, whole, d_seq


@pytest.mark.parametrize("window,bs,ctx", [(16, 8, 100), (20, 8, 77),
                                           (64, 16, 400)])
def test_the_kernels_never_read_a_freed_block(window, bs, ctx):
    """The interpreted kernel over the poisoned pool, the one-row tile and
    an atom whose wide step clamps its blocks into the window: finite, and
    what the oracle gives over the WHOLE context."""
    pools, table, clean, whole, d_seq = poisoned(window, bs, ctx)
    h = 8
    kw = dict(block_size=bs, layer=0, window=window)
    # the next decode step: one row at position ctx, the sequence's next
    q = jax.random.normal(jax.random.PRNGKey(3), (1, h, 16))
    lens = jnp.asarray([ctx + 1], jnp.int32)
    got = paged_decode_attention(q, *pools, table, lens,
                                 impl="pallas_interpret", **kw)
    want = paged_decode_attention(q, *clean, whole, lens, impl="xla", **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the last chunk's atoms, as the forward that cached them ran them: the
    # blocks were given back AFTER it, so poison only what was dead for it
    rows = 8
    for pos0 in (ctx - rows, ctx - 3):
        qlen = ctx - pos0
        early = SequenceDescriptor(uid=2, n_cached=pos0, window_blocks=list(
            range(1, -(-ctx // bs) + 1)))
        early.out_of_window(window, bs)
        lo_blk = int(tile_span(np.int32(pos0), np.int32(qlen), block_size=bs,
                               max_blocks=table.shape[1], window=window,
                               xp=np)[0])
        assert early.window_freed <= lo_blk     # what is freed is skipped
        tab = np.zeros_like(table)
        tab[0, :len(early.window_blocks)] = early.window_blocks
        held = np.zeros(pools[0].shape[1], bool)
        for b in early.window_held:
            held[b * bs:(b + 1) * bs] = True
        dirty = [jnp.where(held[None, :, None, None], p, jnp.nan)
                 for p in clean]
        qa = jax.random.normal(jax.random.PRNGKey(4), (1, rows, h, 16))
        args = (jnp.asarray([pos0], jnp.int32), jnp.asarray([qlen],
                                                            jnp.int32))
        got = ragged_prefill_attention(qa, *dirty, tab, *args,
                                       impl="pallas_interpret", **kw)
        want = ragged_prefill_attention(qa, *clean, whole, *args,
                                        impl="xla", **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_freeing_one_block_early_is_seen():
    """The same poisoned pool with ONE more block given back: the kernel
    reads it (NaN) — the skip and the free agree to the block."""
    window, bs, ctx = 16, 8, 100
    pools, table, *_ = poisoned(window, bs, ctx)
    d = SequenceDescriptor(uid=1, n_cached=ctx, window_blocks=list(
        range(1, -(-(ctx + 1) // bs) + 1)))
    d.out_of_window(window - bs, bs)                   # one block too many
    table = table.copy()
    table[0, :len(d.window_blocks)] = d.window_blocks
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16))
    got = paged_decode_attention(q, *pools, table, jnp.asarray([ctx + 1]),
                                 block_size=bs, layer=0, window=window,
                                 impl="pallas_interpret")
    assert not np.isfinite(np.asarray(got)).all()
