"""Ragged (FastGen-analog) engine tests.

Mirrors the reference's ``tests/unit/inference/v2/ragged/`` (allocator, batch
construction) and model-implementation tests — plus the decisive correctness
check: ragged paged-KV serving must produce exactly what the dense v1 engine
produces for the same prompts.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (BlockedAllocator,
                                                   InferenceEngineV2,
                                                   RaggedInferenceConfig)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (SequenceDescriptor,
                                                          build_ragged_batch)
from deepspeedsyclsupport_tpu.inference.v2.scheduler import schedule_chunks
from deepspeedsyclsupport_tpu.models import build_model
from tests.unit import stream_ends
from tests.unit.greedy import greedy


# -------------------------------------------------------------------- config
@pytest.mark.parametrize("given,says", [
    # an option this engine no longer has: the unknown-key refusal names it
    ({"decode_steps_per_dispatch": 4},
     "unknown ragged config keys: ['decode_steps_per_dispatch']"),
    ({"head_dim_lane_pad": 0},
     "head_dim_lane_pad must be None (auto) or a positive int, got 0"),
    ({"quant_bits": 3}, "quant_bits must be 4 or 8, got 3"),
    ({"eviction_policy": "oldest"},
     "eviction_policy must be longest_context|lru|newest|slack, got 'oldest'"),
    ({"max_context": 100, "block_size": 64},
     "max_context must be a multiple of block_size"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else "")
def test_what_the_ragged_config_refuses_it_refuses_by_name(given, says):
    with pytest.raises(ValueError, match=re.escape(says)):
        RaggedInferenceConfig.from_config(given)


# ----------------------------------------------------------------- allocator
class TestBlockedAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(5)
        assert len(blocks) == 5 and a.free_blocks == 3
        a.free(blocks[:2])
        assert a.free_blocks == 5
        with pytest.raises(RuntimeError):
            a.allocate(6)

    def test_double_free_rejected(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free(b)
        with pytest.raises(ValueError):
            a.free([b[0]])

    def test_invalid_block_rejected(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError):
            a.free([99])


# ------------------------------------------------------------- batch builder
class TestRaggedBatch:
    def test_metadata_layout(self):
        d1 = SequenceDescriptor(uid=1, pending=[10, 11, 12], blocks=[3])
        d2 = SequenceDescriptor(uid=2, pending=[20], n_cached=5,
                                blocks=[7, 1])
        b = build_ragged_batch([(d1, 3), (d2, 1)], max_tokens=8,
                               max_sequences=4, blocks_per_seq=4)
        np.testing.assert_array_equal(b.tokens[:4], [10, 11, 12, 20])
        np.testing.assert_array_equal(b.token_seq[:4], [0, 0, 0, 1])
        np.testing.assert_array_equal(b.token_pos[:4], [0, 1, 2, 5])
        assert b.token_seq[4] == 4  # padding sentinel == max_sequences
        np.testing.assert_array_equal(b.block_tables[0, :1], [3])
        np.testing.assert_array_equal(b.block_tables[1, :2], [7, 1])
        np.testing.assert_array_equal(b.last_tok_idx[:2], [2, 3])
        assert b.uids == [1, 2]
        assert b.current_tokens == 4

    # (cached, scheduled) per chunk, in slot order; atoms of 8 rows
    MIXES = {
        "decoders_and_a_chunked_prompt": [(11, 1), (9, 1), (0, 20), (40, 1),
                                          (3, 1)],
        "a_one_token_prompt": [(0, 1), (16, 9), (7, 1)],
        "prompts_only": [(0, 8), (5, 2), (0, 17)],
        "one_token_chunks_only": [(4, 1), (0, 1), (30, 1)],
    }

    @pytest.mark.parametrize("mix", sorted(MIXES))
    def test_one_token_chunks_take_no_atom(self, mix):
        """A chunk of one token is a row of ``dec_row`` / ``dec_len`` and
        of no live atom; only the longer chunks are cut into atoms, and
        between the two every packed row is reachable exactly once."""
        bq, t_max, s_max = 8, 48, 6
        chunks = [(SequenceDescriptor(uid=i, pending=list(range(1, n + 1)),
                                      n_cached=c, blocks=[i]), n)
                  for i, (c, n) in enumerate(self.MIXES[mix])]
        b = build_ragged_batch(chunks, t_max, s_max, 8, atom_q=bq)
        dead = (b.atom_qidx.shape[0] - 1) * bq
        live = np.flatnonzero(b.atom_qlen)
        assert len(live) == b.live_atoms == sum(
            -(-n // bq) for _d, n in chunks if n > 1)
        reached, cur = [], 0
        for slot, (d, n) in enumerate(chunks):
            if n == 1:
                assert (b.dec_row[slot], b.dec_len[slot]) == (
                    cur, d.n_cached + 1)
                assert b.atom_inv[cur] == dead     # as padding
                reached.append(cur)
            else:
                assert b.dec_len[slot] == 0
            cur += n
        assert not b.dec_len[len(chunks):].any()
        for a in live:
            rows = b.atom_qidx[a, :b.atom_qlen[a]]
            np.testing.assert_array_equal(       # the gather's inverse
                b.atom_inv[rows], a * bq + np.arange(len(rows)))
            seq = set(b.token_seq[rows])
            assert len(seq) == 1 and chunks[seq.pop()][1] > 1
            np.testing.assert_array_equal(
                b.token_pos[rows], b.atom_pos0[a] + np.arange(len(rows)))
            reached += list(rows)
        assert sorted(reached) == list(range(b.current_tokens))
        assert (b.atom_inv[b.current_tokens:] == dead).all()
        assert b.atom_qlen[-1] == 0
        assert len(b.tile_args) == 7

    def test_a_batch_built_without_atoms_has_no_tile_args(self):
        d = SequenceDescriptor(uid=1, pending=[5], n_cached=3, blocks=[0])
        b = build_ragged_batch([(d, 1)], max_tokens=4, max_sequences=2,
                               blocks_per_seq=2)
        assert b.tile_args == () and b.dec_len is None and not b.live_atoms

    def test_budget_overflow_rejected(self):
        d = SequenceDescriptor(uid=1, pending=list(range(10)))
        with pytest.raises(ValueError):
            build_ragged_batch([(d, 10)], max_tokens=4, max_sequences=2,
                               blocks_per_seq=2)


# --------------------------------------------------------------- scheduler
class TestSplitFuse:
    def _mk(self, uid, pending, cached=0):
        return SequenceDescriptor(uid=uid, pending=list(pending),
                                  n_cached=cached)

    def test_decode_first_then_prompt_split(self):
        alloc = BlockedAllocator(64)
        dec = self._mk(1, [7], cached=20)
        dec.blocks = alloc.allocate(3)  # 20 cached / bs=8 → 3 blocks
        long_prompt = self._mk(2, range(100))
        chunks = schedule_chunks([dec, long_prompt], alloc, max_tokens=16,
                                 max_sequences=8, block_size=8,
                                 max_context=256)
        assert chunks[0][0] is dec and chunks[0][1] == 1
        assert chunks[1][0] is long_prompt and chunks[1][1] == 15  # split
        assert sum(n for _, n in chunks) == 16  # budget filled exactly

    def test_fuse_short_prompts(self):
        alloc = BlockedAllocator(64)
        seqs = [self._mk(i, range(4)) for i in range(3)]
        chunks = schedule_chunks(seqs, alloc, max_tokens=16, max_sequences=8,
                                 block_size=8, max_context=64)
        assert [(c[0].uid, c[1]) for c in chunks] == [(0, 4), (1, 4), (2, 4)]

    def test_kv_pressure_blocks_admission(self):
        alloc = BlockedAllocator(2)  # only 2 blocks of 8 → 16 tokens total
        a, b = self._mk(1, range(16)), self._mk(2, range(8))
        chunks = schedule_chunks([a, b], alloc, max_tokens=64, max_sequences=8,
                                 block_size=8, max_context=64)
        assert len(chunks) == 1 and chunks[0][0] is a  # b couldn't get blocks

    def test_prefill_fraction_caps_prompt_share(self):
        """max_prefill_fraction bounds prompt tokens when decodes ride the
        same forward (ITL protection); pure-prefill forwards ignore it."""
        alloc = BlockedAllocator(64)
        dec = self._mk(1, [7], cached=8)
        dec.blocks = alloc.allocate(1)
        prompt = self._mk(2, range(100))
        chunks = schedule_chunks([dec, prompt], alloc, max_tokens=16,
                                 max_sequences=8, block_size=8,
                                 max_context=256, max_prefill_fraction=0.25)
        assert chunks[0][0] is dec
        assert chunks[1][0] is prompt and chunks[1][1] == 4  # 16 * 0.25
        # no decodes live → the prompt may fill the whole budget
        alloc2 = BlockedAllocator(64)
        p2 = self._mk(3, range(100))
        chunks = schedule_chunks([p2], alloc2, max_tokens=16,
                                 max_sequences=8, block_size=8,
                                 max_context=256, max_prefill_fraction=0.25)
        assert chunks[0][1] == 16

    def test_prefill_fairness_least_recently_scheduled_first(self):
        alloc = BlockedAllocator(2)  # room for ONE 8-token chunk per pass
        fresh = self._mk(1, range(8))
        fresh.last_scheduled = 5     # served recently
        starved = self._mk(2, range(8))
        starved.last_scheduled = 1   # kept losing admission races
        chunks = schedule_chunks([fresh, starved], alloc, max_tokens=8,
                                 max_sequences=8, block_size=8,
                                 max_context=64)
        assert chunks[0][0] is starved  # round-robin, not arrival order


# ------------------------------------------------------------ engine parity
@pytest.fixture(scope="module")
def tiny():
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def _v2(model, params, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_tokens_per_batch", 16)
    kw.setdefault("max_sequences", 4)
    return InferenceEngineV2(model, params, **kw)


class TestEngineV2:
    def test_put_query_flush_contract(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        out = eng.put([11], [[1, 5, 9]])
        assert 11 in out and out[11].shape == (model.config.vocab_size,)
        assert eng.query(11) is not None
        assert eng.query(999) is None
        used = eng.allocator.free_blocks
        eng.flush([11])
        assert eng.allocator.free_blocks > used  # blocks returned
        assert eng.query(11) is None

    def test_lane_padded_kv_pool_parity(self, tiny):
        """Mosaic requires the paged-kernel pool's head dim be lane-tile
        (128) aligned on real TPU; the pool is allocated padded, q/k/v
        padded at the attention seam with q pre-scaled to compensate the
        impls' 1/sqrt(padded-dim) softmax scale
        (kv_cache.lane_padded_head_dim). Forcing the padding on the CPU sim
        must leave LOGITS numerically equal to the unpadded engine — greedy
        alone could mask a mis-scaled softmax (caught in review: the scale
        used to come from the padded dim, a 2.8x colder softmax at d=16)."""
        model, params = tiny
        prompt = [1, 5, 9, 200, 3]
        base = np.asarray(_v2(model, params).put([1], [prompt])[1])
        eng = _v2(model, params, head_dim_lane_pad=128)
        assert eng.kv.k.shape[-1] == 128  # pool really is padded
        got = np.asarray(eng.put([1], [prompt])[1])
        np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)
        want = greedy(model, params, prompt, 6)
        toks = eng.generate([prompt], max_new_tokens=6)[0]
        assert list(toks) == want, (toks, want)

    def test_expert_and_tensor_parallel_serving_parity(self):
        """MoE serving over an expert-parallel (and TP-composed) topology —
        the reference's DeepSpeedMoEInference EP story: declarative expert
        shardings partition the grouped GEMMs, logits bit-match the
        replicated engine."""
        import deepspeedsyclsupport_tpu as ds
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)

        model = build_model("tiny-moe", dtype="float32")
        params = model.init_params()
        prompt = [1, 5, 9, 200, 3]

        def serve(**axes):
            reset_world_topology()
            topo = ds.build_topology(dp=-1, **axes)
            eng = InferenceEngineV2(model, params, dtype=jnp.float32,
                                    block_size=8, max_context=64,
                                    max_tokens_per_batch=16, topology=topo)
            out = np.asarray(eng.put([1], [prompt])[1])
            eng.flush([1])
            return out

        base = serve()
        np.testing.assert_allclose(serve(ep=2), base, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(serve(ep=2, tp=2), base, rtol=1e-5,
                                   atol=1e-5)

    def test_eviction_policy_selects_victim(self, tiny):
        """generate() under KV pressure sheds the victim the configured
        policy names (VERDICT r3 weak #6: longest-evict was the only
        option)."""
        model, params = tiny
        for policy in ("longest_context", "lru", "newest"):
            eng = _v2(model, params, eviction_policy=policy,
                      max_sequences=3)
            outs = eng.generate([[1, 2, 3], [4, 5], [6]], max_new_tokens=4)
            assert len(outs) == 3 and all(len(o) >= 1 for o in outs)
            eng.flush(list(eng.seqs))
        import pytest as _p

        with _p.raises(ValueError, match="eviction_policy"):
            _v2(model, params, eviction_policy="coinflip")
        with _p.raises(ValueError, match="max_prefill_fraction"):
            _v2(model, params, max_prefill_fraction=0.0)

    def test_duplicate_uid_in_one_put_rejected(self, tiny):
        """A repeated uid's second entry is checked against pre-call state,
        so double admission could push pending past max_context and wedge
        the sequence — duplicates are rejected structurally instead."""
        model, params = tiny
        eng = _v2(model, params)
        out = eng.put([7, 7], [[1, 2, 3], [4, 5]])
        assert 7 in out.admission.admitted          # first entry admitted
        assert 7 in out.admission.rejected          # second entry rejected
        assert "duplicate" in out.admission.reasons[7]
        # only the FIRST entry's tokens were enqueued and drained
        assert eng.seqs[7].n_cached == 3
        dense = model.apply(params, jnp.asarray([[1, 2, 3]], jnp.int32))
        np.testing.assert_allclose(out[7], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)
        assert not eng.can_schedule([9, 9], [1, 1])
        eng.flush([7])

    def test_prefill_logits_match_dense(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        prompt = [1, 5, 9, 200, 3]
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)

    def test_split_prompt_matches_dense(self, tiny):
        """A prompt longer than the token budget is split across forwards yet
        must give the same final logits."""
        model, params = tiny
        eng = _v2(model, params, max_tokens_per_batch=8)
        prompt = list(np.random.RandomState(0).randint(1, 500, size=20))
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)

    def test_generate_matches_naive(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        prompts = [[7, 3, 11], [4, 100, 42, 8, 19]]
        got = eng.generate(prompts, max_new_tokens=6)
        for p, g in zip(prompts, got):
            assert g == greedy(model, params, p, 6)

    def test_moe_prefill_logits_match_dense(self):
        """MoE ragged serving (reference moe_scatter/grouped-GEMM/moe_gather):
        v2 must serve tiny-moe with logits parity vs the dense forward.
        capacity_factor is raised so the training-path capacity buffers never
        truncate — the serving path is exact by construction."""
        model = build_model("tiny-moe", dtype="float32", capacity_factor=16.0)
        params = model.init_params()
        eng = _v2(model, params)
        prompt = [1, 5, 9, 200, 3]
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)

    def test_moe_generate_matches_naive(self):
        """Greedy decode parity over the MoE ragged + decode fast paths —
        the TestV1V2Parity shape from the round-1 verdict."""
        model = build_model("tiny-moe", dtype="float32", capacity_factor=16.0)
        params = model.init_params()
        eng = _v2(model, params)
        prompts = [[7, 3, 11], [4, 100, 42, 8, 19]]
        got = eng.generate(prompts, max_new_tokens=6)
        for p, g in zip(prompts, got):
            assert g == greedy(model, params, p, 6)

    def test_moe_nodrop_matches_capacity_path(self):
        """Unit parity: grouped-GEMM no-drop MoE == capacity-einsum MoE when
        capacity never truncates."""
        from deepspeedsyclsupport_tpu.models import get_config
        from deepspeedsyclsupport_tpu.parallel import moe_mlp, moe_mlp_nodrop

        cfg = get_config("tiny-moe", capacity_factor=16.0)
        model = build_model(cfg)
        p = model.init_params()["layers"]["moe"]
        p0 = jax.tree_util.tree_map(lambda x: x[0], p)  # layer 0 weights
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, cfg.hidden_size))
        want, _ = moe_mlp(p0, x, cfg)
        got, rows = moe_mlp_nodrop(p0, x[0], cfg)
        assert int(rows.sum()) == 24 * cfg.num_experts_per_tok
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                                   rtol=2e-4, atol=2e-4)

    def test_continuous_batching_oversubscribed(self, tiny):
        """More prompts than max_sequences: engine must admit in waves and
        still produce exact per-prompt results."""
        model, params = tiny
        eng = _v2(model, params, max_sequences=2)
        rs = np.random.RandomState(1)
        prompts = [list(rs.randint(1, 500, size=rs.randint(2, 6)))
                   for _ in range(5)]
        got = eng.generate(prompts, max_new_tokens=4)
        for p, g in zip(prompts, got):
            assert g == greedy(model, params, p, 4)

    def test_context_cap_truncates_not_crashes(self, tiny):
        """A sequence hitting max_context retires with truncated output;
        other in-flight sequences keep their results (regression: used to
        RuntimeError the whole batch)."""
        model, params = tiny
        eng = _v2(model, params, max_context=16, block_size=8)
        long_p = list(np.random.RandomState(2).randint(1, 500, size=14))
        short_p = [7, 3]
        got = eng.generate([long_p, short_p], max_new_tokens=8)
        assert len(got[0]) <= 8  # truncated at context cap (14 + n <= 16)
        assert len(got[0]) >= 2
        assert got[1] == greedy(model, params, short_p, 8)

    def test_empty_prompt_returns_empty(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        got = eng.generate([[], [7, 3, 11]], max_new_tokens=3)
        assert got[0] == []
        assert got[1] == greedy(model, params, [7, 3, 11], 3)

    def test_oversized_prompt_rejected(self, tiny):
        model, params = tiny
        eng = _v2(model, params, max_context=16, block_size=8)
        with pytest.raises(ValueError):
            eng.generate([list(range(1, 30))], max_new_tokens=2)

    def test_kv_pool_eviction_progresses(self, tiny):
        """Tiny KV pool forces mid-decode eviction; every sequence still
        returns a (possibly truncated) result instead of crashing."""
        model, params = tiny
        eng = _v2(model, params, num_blocks=4, block_size=8, max_context=32)
        prompts = [[1, 2, 3], [4, 5, 6], [7, 8, 9]]
        got = eng.generate(prompts, max_new_tokens=6)
        assert all(len(g) >= 1 for g in got)
        assert eng.allocator.free_blocks == 4  # everything reclaimed

    def test_can_schedule_limits(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        assert eng.can_schedule([1], [10])
        assert not eng.can_schedule([1], [100])            # > max_context
        assert not eng.can_schedule(list(range(9)), [1] * 9)  # > max_sequences

    def test_check_schedule_structured(self, tiny):
        """Per-uid admission: the schedulable prefix admits, the rest reject
        with named reasons (reference can_schedule:179 contract — the
        serving layer backs off per sequence, no exception)."""
        model, params = tiny
        eng = _v2(model, params)
        res = eng.check_schedule([1, 2, 3], [10, 100, 10])
        assert res.admitted == (1, 3) and res.rejected == (2,)
        assert "max_context" in res.reasons[2]
        assert not bool(res) and bool(eng.check_schedule([1], [4]))
        # slot pressure: uids beyond max_sequences (4 here) reject as "slots"
        res = eng.check_schedule(list(range(9)), [1] * 9)
        assert len(res.admitted) == 4 and "slots" in res.reasons[4]

    def test_put_structured_rejection(self, tiny):
        """put() admits what fits and reports the rest in .admission instead
        of raising; strict=True restores the raising contract."""
        model, params = tiny
        eng = _v2(model, params, max_context=16, block_size=8)
        out = eng.put([1, 2], [[7, 3, 11], list(range(1, 30))])
        assert out.admission.admitted == (1,)
        assert out.admission.rejected == (2,)
        assert 1 in out and 2 not in out           # admitted seq ran fully
        assert 2 not in eng.seqs                   # rejected seq not enqueued
        with pytest.raises(RuntimeError):
            eng.put([3], [list(range(1, 30))], strict=True)


class TestPackedFlashPrefill:
    """The chunked-prefill flash path (VERDICT round-1 weak #3): per-sequence
    KV gather + packed ragged cross-attention through the Pallas kernel must
    match the exact per-token XLA reference."""

    def _setup(self, seed=0):
        from deepspeedsyclsupport_tpu.inference.v2.model import (
            _packed_flash_attention, _paged_attention)

        rng = np.random.RandomState(seed)
        s, bps, bs, kvh, h, d = 3, 4, 8, 2, 4, 16
        num_slots = 96  # covers every slot the 3x4 block table addresses
        k_cache = jnp.asarray(rng.randn(num_slots + 1, kvh, d), jnp.float32)
        v_cache = jnp.asarray(rng.randn(num_slots + 1, kvh, d), jnp.float32)
        # seq i owns blocks [i*4, i*4+4)
        block_tables = jnp.arange(s * bps, dtype=jnp.int32).reshape(s, bps)
        # mixed batch: seq0 chunk of 5 @ pos 0.., seq1 decode 1 @ pos 9,
        # seq2 chunk of 3 @ pos 4.., plus 3 pad tokens
        token_seq = jnp.asarray([0] * 5 + [1] + [2] * 3 + [3] * 3, jnp.int32)
        token_pos = jnp.asarray(list(range(5)) + [9] + [4, 5, 6] + [0, 0, 0],
                                jnp.int32)
        t = token_seq.shape[0]
        q = jnp.asarray(rng.randn(t, h, d), jnp.float32)
        return (_packed_flash_attention, _paged_attention, q, k_cache,
                v_cache, token_seq, token_pos, block_tables, bs)

    def test_matches_paged_reference(self):
        (flash, paged, q, kc, vc, tseq, tpos, bt, bs) = self._setup()
        want = paged(q, kc, vc, tseq, tpos, bt, bs)
        got = flash(q, kc, vc, tseq, tpos, bt, bs)
        # pad tokens (seq id 3 == S) are garbage in the reference; compare
        # real tokens only
        np.testing.assert_allclose(np.asarray(got)[:9], np.asarray(want)[:9],
                                   rtol=2e-4, atol=2e-4)

    def test_engine_serves_with_flash_prefill(self, tiny):
        model, params = tiny
        eng = _v2(model, params, prefill_attn="flash")
        prompts = [[7, 3, 11], [4, 100, 42, 8, 19]]
        got = eng.generate(prompts, max_new_tokens=6)
        for p, g in zip(prompts, got):
            assert g == greedy(model, params, p, 6)

    def test_split_prompt_with_flash_prefill(self, tiny):
        model, params = tiny
        eng = _v2(model, params, prefill_attn="flash",
                  max_tokens_per_batch=8)
        prompt = list(np.random.RandomState(0).randint(1, 500, size=20))
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- arch zoo serving
class TestArchZooServing:
    """The v2 ragged engine must serve every architecture-config axis the
    training model supports (the reference's v2 model zoo —
    ``inference/v2/model_implementations/{opt,falcon,phi,...}`` — as config
    presets): layernorm, learned/alibi positions, partial rotary, standard
    MLP, parallel blocks, biases, sliding window."""

    def _shrunk(self, name, **kw):
        import dataclasses

        from deepspeedsyclsupport_tpu.models import get_config

        cfg = get_config(name)
        return dataclasses.replace(
            cfg, vocab_size=512, hidden_size=64, intermediate_size=96,
            num_layers=2, num_heads=4,
            num_kv_heads=min(cfg.num_kv_heads or 4, 4), head_dim=None,
            max_seq_len=64, dtype="float32", **kw)

    @pytest.mark.parametrize("name", ["gpt2-small", "opt-1.3b", "bloom-7b1",
                                      "falcon-7b", "phi-2", "gpt-neox-20b",
                                      "gptj-6b"])
    def test_prefill_logits_match_dense(self, name):
        model = build_model(self._shrunk(name))
        params = model.init_params()
        eng = _v2(model, params)
        prompt = [1, 5, 9, 200, 3]
        out = eng.put([1], [prompt])
        dense = model.apply(params, jnp.asarray([prompt], jnp.int32))
        np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("name", ["bloom-7b1", "gpt-neox-20b"])
    def test_generate_matches_naive(self, name):
        """Greedy decode through BOTH v2 paths (ragged prefill + paged decode
        fast path) for alibi and parallel-block/partial-rotary archs."""
        model = build_model(self._shrunk(name))
        params = model.init_params()
        eng = _v2(model, params)
        prompts = [[7, 3, 11], [4, 100, 42, 8, 19]]
        got = eng.generate(prompts, max_new_tokens=6)
        for p, g in zip(prompts, got):
            assert g == greedy(model, params, p, 6)

    def test_sliding_window_generate(self):
        """Mistral-style sliding window must serve consistently: v2 greedy ==
        naive dense greedy (both windowed)."""
        model = build_model(self._shrunk("tiny", sliding_window=4))
        params = model.init_params()
        eng = _v2(model, params)
        prompts = [[7, 3, 11, 8, 2, 90, 17, 44]]
        got = eng.generate(prompts, max_new_tokens=5)
        assert got[0] == greedy(model, params, prompts[0], 5)


class TestSerialize:
    """Engine snapshot round-trip (reference engine_v2.serialize:237)."""

    def test_serialize_deserialize_logits_match(self, tiny, tmp_path):
        model, params = tiny
        eng = _v2(model, params)
        prompt = [1, 5, 9, 200, 3]
        want = eng.put([1], [prompt])[1]
        eng.serialize(str(tmp_path / "snap"))
        eng2 = InferenceEngineV2.deserialize(str(tmp_path / "snap"))
        assert eng2.config.block_size == eng.config.block_size
        got = eng2.put([1], [prompt])[1]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_serialize_dequantizes_zero_inference(self, tiny, tmp_path):
        model, params = tiny
        eng = _v2(model, params, quantize_weights=True)
        eng.serialize(str(tmp_path / "qsnap"))
        eng2 = InferenceEngineV2.deserialize(str(tmp_path / "qsnap"))
        prompt = [7, 3, 11]
        a = eng.put([1], [prompt])[1]
        b = eng2.put([1], [prompt])[1]
        np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-2)


class TestWarmup:
    def test_warmup_leaves_engine_clean_and_serving_exact(self, tiny):
        """warmup() compiles both KV-sharding states, releases all its
        state, and does not perturb subsequent decoding."""
        model, params = tiny
        eng = _v2(model, params)
        eng.warmup()
        assert not eng.seqs
        assert eng.allocator.free_blocks == eng.config.num_blocks
        prompt = [7, 3, 11]
        got = eng.generate([prompt], max_new_tokens=4)[0]
        assert got == greedy(model, params, prompt, 4)


class TestSampledGenerate:
    def test_sampled_decode_respects_budget_and_eos(self, tiny):
        """Sampled streams stop at their budget or at the first EOS they
        draw (emitted, then nothing), and the engine ends empty."""
        model, params = tiny
        eng = _v2(model, params)
        kw = dict(max_new_tokens=7, do_sample=True, temperature=0.8,
                  top_k=20, rng=jax.random.PRNGKey(3))
        plain = eng.generate([[7, 3, 11], [4, 9]], **kw)
        assert [len(g) for g in plain] == [7, 7]
        eos = plain[0][3]
        got = eng.generate([[7, 3, 11], [4, 9]], eos_token_id=eos, **kw)
        # the same key draws the same tokens until a stream leaves the batch
        assert got[0] == plain[0][:plain[0].index(eos) + 1]
        assert all(1 <= len(g) <= 7 and eos not in g[:-1] for g in got)
        assert not eng.seqs
        assert eng.allocator.free_blocks == eng.config.num_blocks

    def test_temperature_topp_eos_do_not_recompile(self, tiny):
        """temperature/top_p are traced operands and the EOS is the host's:
        sweeping them reuses ONE compiled sampler (only structure —
        do_sample/top_k/top_p-active — is static)."""
        model, params = tiny
        eng = _v2(model, params)
        compiled = []
        for i, (t, p, eos) in enumerate([(0.7, 0.9, None), (1.3, 0.8, 42),
                                         (0.5, 0.95, 7)]):
            eng.generate([[7, 3, 11]], max_new_tokens=4, do_sample=True,
                         temperature=t, top_p=p, eos_token_id=eos,
                         rng=jax.random.PRNGKey(i))
            # jit's cache is by the function, shared with earlier engines
            compiled.append(eng._sample_fn._cache_size())
        assert compiled[0] == compiled[1] == compiled[2]


# ------------------------------------------------- a stream that ends early
@pytest.fixture(scope="module")
def ending(tiny):
    model, params = tiny
    return stream_ends.family(_v2(model, params, max_context=32,
                                  num_blocks=12))


@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    stream_ends.check(ending, driver, end)
