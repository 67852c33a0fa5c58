"""Cross-request KV prefix cache tests (``inference/v2/prefix_cache.py`` +
the refcounted ``BlockedAllocator`` + the engine/serving integration).

Invariants proven here, per docs/serving.md "prefix reuse":

* refcount lifecycle — a block frees only when its LAST holder releases;
  double free and retain-of-free are impossible by construction
* ``kv_pool_stats`` physical vs logical — the gap is the HBM sharing saves
* block-aligned probe (≥ 1 novel token), tenant scoping, ``min_block_hits``
  deferral, ``max_pinned_blocks`` LRU, pressure ``reclaim`` skipping shared
  pins
* byte-identical outputs cache-on vs cache-off — through plain admission,
  KV-exhaustion evict + requeue, AND crash replay sharing blocks with a
  live stream whose donor then evicts (the PR 16 journal contract holds
  with shared blocks)
* ``Serve/prefix.*`` registration under strict events
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (
    BlockedAllocator, CapacityModel, InferenceEngineV2, ServingPolicyConfig,
    ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (
    kv_pool_stats)
from deepspeedsyclsupport_tpu.inference.v2.prefix_cache import (
    PrefixCache, chain_hash)
from deepspeedsyclsupport_tpu.inference.v2.serving import (
    SERVE_PREFIX)
from deepspeedsyclsupport_tpu.models import build_model
from tests.unit.greedy import greedy


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


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


def _engine_greedy(eng, uid, prompt, n):
    """Greedy decode through put() — the engine-level byte-identity probe
    (exercises mapped prefixes, CoW guards and the commit path)."""
    logits = eng.put([uid], [list(prompt)])[uid]
    out = []
    for _ in range(n):
        nxt = int(jnp.argmax(logits))
        out.append(nxt)
        logits = eng.put([uid], [[nxt]])[uid]
    eng.flush([uid])
    return out


def _drain(sess, out=None, clock=None, max_steps=500):
    events = []
    steps = 0
    while not sess.idle:
        if clock is not None:
            clock.advance(0.05)
        evs = sess.step()
        events.extend(evs)
        if out is not None:
            for e in evs:
                if e.kind == "token":
                    out.setdefault(e.uid, []).extend(e.tokens)
        steps += 1
        assert steps < max_steps, "session did not converge"
    return events


# SYSTEM covers two full 8-token blocks; tails diverge per request
SYSTEM = list(range(40, 56))
TAILS = {1: [3, 7, 11], 2: [9, 2], 3: [5, 5, 6, 1], 4: [8]}


# ======================================================= allocator refcounts
class TestAllocatorRefcounts:
    def test_last_holder_frees(self):
        a = BlockedAllocator(4)
        (b,) = a.allocate(1)
        assert a.refcount(b) == 1 and a.free_blocks == 3
        a.retain([b])
        assert a.refcount(b) == 2 and a.free_blocks == 3
        a.release([b])
        assert a.refcount(b) == 1 and a.free_blocks == 3, \
            "first release must NOT free a shared block"
        a.release([b])
        assert a.refcount(b) == 0 and a.free_blocks == 4

    def test_double_free_impossible(self):
        a = BlockedAllocator(2)
        (b,) = a.allocate(1)
        a.free([b])  # legacy alias routes through the refcounted release
        with pytest.raises(ValueError, match="double free"):
            a.release([b])

    def test_retain_of_free_block_raises(self):
        a = BlockedAllocator(2)
        with pytest.raises(ValueError, match="retain of free"):
            a.retain([0])

    def test_logical_and_shared_accounting(self):
        a = BlockedAllocator(4)
        b1, b2 = a.allocate(2)
        a.retain([b1])
        a.retain([b1])
        assert a.logical_blocks == 4  # 3 holders of b1 + 1 of b2
        assert a.shared_blocks == 1   # only b1 has > 1 holder
        a.release([b1])
        a.release([b1])
        assert a.shared_blocks == 0 and a.logical_blocks == 2

    def test_reclaim_cb_relieves_pressure(self):
        a = BlockedAllocator(2)
        held = a.allocate(2)
        released = []

        def cb(n):
            a.release([held[0]])
            released.append(n)
            return 1

        a.reclaim_cb = cb
        got = a.try_allocate(1)
        assert got is not None and released == [1]


# ======================================================== prefix-cache units
def _index_prompt(pc, alloc, tokens, tenant="default"):
    """Allocate + offer every full block of ``tokens`` (engine commit path
    in miniature); the blocks' sole holder is then the index pin."""
    bs = pc.block_size
    n = len(tokens) // bs
    blocks = alloc.allocate(n)
    h = b""
    for i, b in enumerate(blocks):
        h = chain_hash(h, tokens[i * bs:(i + 1) * bs])
        pc.offer(tenant, h, b)
    # drop the "stream's" reference: the index pin keeps the blocks live
    alloc.release(blocks)
    return blocks


class TestPrefixCacheUnits:
    def test_probe_is_block_aligned_with_one_novel_token(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4)
        toks = list(range(100, 108))  # exactly 2 full blocks
        blocks = _index_prompt(pc, a, toks)
        # a probe of exactly 2 blocks may match only 1 — at least one
        # token must run a forward to produce logits
        got, _, cached = pc.probe(toks)
        assert got == blocks[:1] and cached == 4
        got, _, cached = pc.probe(toks + [1])
        assert got == blocks and cached == 8
        # interior divergence breaks the chain at the diverging block
        got, _, cached = pc.probe([toks[0] + 1] + toks[1:] + [1])
        assert got == [] and cached == 0

    def test_peek_has_no_side_effects(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4)
        _index_prompt(pc, a, list(range(8)))
        before = dict(pc.counters)
        assert pc.peek(list(range(8)) + [9]) == 8
        assert pc.counters == before

    def test_tenant_scoping(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4, scope="tenant")
        toks = list(range(9))
        _index_prompt(pc, a, toks[:8], tenant="alice")
        assert pc.peek(toks, tenant="alice") == 8
        assert pc.peek(toks, tenant="bob") == 0, \
            "one tenant's prompts must be invisible to another's probes"
        g = PrefixCache(BlockedAllocator(8), 4, scope="global")
        _index_prompt(g, g.allocator, toks[:8], tenant="alice")
        assert g.peek(toks, tenant="bob") == 8

    def test_min_block_hits_defers_pin(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4, min_block_hits=2)
        (b,) = a.allocate(1)
        h = chain_hash(b"", [1, 2, 3, 4])
        assert pc.offer("default", h, b) is False
        assert pc.pinned_blocks == 0 and a.refcount(b) == 1
        assert pc.offer("default", h, b) is True
        assert pc.pinned_blocks == 1 and a.refcount(b) == 2

    def test_max_pinned_blocks_lru(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4, max_pinned_blocks=2)
        b1 = _index_prompt(pc, a, [1, 2, 3, 4])[0]
        b2 = _index_prompt(pc, a, [5, 6, 7, 8])[0]
        # touch b1 so b2 is the LRU entry when the cap overflows
        assert pc.peek([1, 2, 3, 4, 9], ) == 4
        pc.probe([1, 2, 3, 4, 9])
        b3 = _index_prompt(pc, a, [9, 10, 11, 12])[0]
        assert pc.pinned_blocks == 2
        assert a.refcount(b2) == 0, "LRU entry must be unpinned (and freed)"
        assert a.refcount(b1) == 1 and a.refcount(b3) == 1
        assert pc.counters["unpins"] == 1

    def test_reclaim_skips_shared_pins(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4)
        b1 = _index_prompt(pc, a, [1, 2, 3, 4])[0]
        b2 = _index_prompt(pc, a, [5, 6, 7, 8])[0]
        a.retain([b1])  # a live stream maps b1
        assert pc.reclaimable() == 1
        freed = pc.reclaim(2)
        assert freed == 1
        assert a.refcount(b1) == 2, "shared pin must survive reclaim"
        assert a.refcount(b2) == 0
        a.release([b1])

    def test_invalidate_releases_every_pin(self):
        a = BlockedAllocator(8)
        pc = PrefixCache(a, 4)
        _index_prompt(pc, a, list(range(8)))
        _index_prompt(pc, a, list(range(20, 28)))
        assert a.free_blocks == 4
        assert pc.invalidate() == 4
        assert pc.pinned_blocks == 0 and a.free_blocks == 8

    def test_config_validation(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError, match="scope"):
            PrefixCache(a, 4, scope="everyone")
        with pytest.raises(ValueError, match="min_block_hits"):
            PrefixCache(a, 4, min_block_hits=0)
        with pytest.raises(ValueError, match="max_pinned_blocks"):
            PrefixCache(a, 4, max_pinned_blocks=0)
        with pytest.raises(ValueError, match="prefix_cache"):
            ServingPolicyConfig(prefix_cache={"enabled": True, "bogus": 1})


# ==================================================== engine integration
class TestEnginePrefixIntegration:
    def test_mapped_prefix_shares_blocks_and_stats(self, tiny):
        model, params = tiny
        eng = _v2(model, params)
        pc = eng.install_prefix_cache()
        eng.put([1], [SYSTEM + TAILS[1]])
        assert pc.pinned_blocks == 2  # both full SYSTEM blocks indexed
        donor_blocks = list(eng.seqs[1].blocks[:2])
        eng.put([2], [SYSTEM + TAILS[2]])
        d2 = eng.seqs[2]
        assert d2.cached_prefix_len == 16 and d2.n_cached >= 16
        assert d2.blocks[:2] == donor_blocks
        # holders of each shared block: donor stream + index + sharer
        assert all(eng.allocator.refcount(b) == 3 for b in donor_blocks)
        st = kv_pool_stats(eng.kv, eng.allocator)
        assert st["blocks_shared"] == 2
        assert st["blocks_logical"] == st["blocks_physical"] + 4
        assert st["logical_occupancy"] > st["occupancy"]
        assert pc.counters["hits"] == 1 and pc.counters["tokens_saved"] == 16
        eng.flush([1, 2])
        # streams gone; only the index pins remain, and they are reclaimable
        assert pc.reclaimable() == 2
        eng.uninstall_prefix_cache()
        assert eng.allocator.free_blocks == eng.config.num_blocks

    def test_byte_identity_and_no_cow_in_steady_state(self, tiny):
        model, params = tiny
        want = {u: greedy(model, params, SYSTEM + TAILS[u], 5)
                for u in (1, 2, 3)}
        eng = _v2(model, params)
        pc = eng.install_prefix_cache()
        for u in (1, 2, 3):
            got = _engine_greedy(eng, u, SYSTEM + TAILS[u], 5)
            assert got == want[u], f"uid {u} diverged under prefix sharing"
        assert pc.counters["hits"] == 2  # streams 2 and 3 reuse stream 1's
        # block alignment keeps writes out of shared blocks: the CoW guard
        # (defense-in-depth) must never actually fire
        assert pc.counters["cow_copies"] == 0

    def test_donor_preempt_keeps_sharer_intact(self, tiny):
        model, params = tiny
        want = greedy(model, params, SYSTEM + TAILS[2], 5)
        eng = _v2(model, params)
        pc = eng.install_prefix_cache()
        eng.put([1], [SYSTEM + TAILS[1]])           # donor commits SYSTEM
        logits = eng.put([2], [SYSTEM + TAILS[2]])[2]  # sharer maps it
        shared = list(eng.seqs[2].blocks[:2])
        eng.preempt(1)                               # donor evicts
        assert pc.pinned_blocks == 2, "index pins survive the donor"
        assert all(eng.allocator.refcount(b) == 2 for b in shared)
        out = []
        for _ in range(5):
            nxt = int(jnp.argmax(logits))
            out.append(nxt)
            logits = eng.put([2], [[nxt]])[2]
        assert out == want, "sharer must stay byte-identical after donor evict"

    def test_check_schedule_prices_novel_blocks_only(self, tiny):
        model, params = tiny
        eng = _v2(model, params, num_blocks=5, block_size=8, max_context=40)
        eng.install_prefix_cache()
        # donor stays LIVE: its 3 blocks are held, the 2 index pins are
        # shared with it (refcount 2 → not reclaimable), 2 blocks free
        eng.put([1], [SYSTEM + [1]])   # 2 full blocks indexed, 3rd partial
        cold = eng.check_schedule([2], [17], cached_prefix={2: 0})
        assert 2 in cold.rejected and "kv" in cold.reasons[2]
        # same prompt with the 16-token cached prefix: 2 of its 3 blocks
        # arrive shared, so only 1 novel block is priced — admits
        res = eng.check_schedule([2], [17], cached_prefix={2: 16})
        assert 2 in res.admitted
        eng.flush([1])


# =================================================== serving-session e2e
def _mk_sess(eng, clock, *, prefix, journal_path=None, **pol):
    cap = CapacityModel(prefill_tok_s=1e6, decode_step_s=1e-4)
    pc = prefix if isinstance(prefix, dict) else \
        ({"enabled": True} if prefix else None)
    cfg = ServingPolicyConfig(prefix_cache=pc, journal_path=journal_path,
                              **pol)
    return ServingSession(eng, cfg, clock=clock, capacity=cap)


class TestServingPrefixE2E:
    def test_byte_identity_on_vs_off_with_eviction_and_requeue(self, tiny):
        """The satellite-3 E2E: a pool small enough to force evict+requeue
        mid-run, sequential waves sharing SYSTEM, cache on vs off — the
        outputs must be byte-identical and the on-arm must actually hit."""
        model, params = tiny
        outs = {}
        stats = {}
        for arm in ("off", "on"):
            eng = _v2(model, params, num_blocks=10, block_size=8,
                      max_context=40, max_sequences=3)
            clock = FakeClock()
            sess = _mk_sess(eng, clock, prefix=(arm == "on"),
                            preempt_policy="requeue")
            out = {}
            # wave 1 seeds the cache; waves 2+ share SYSTEM and contend
            # for a pool that cannot hold 3 full streams + pins
            for uid in (1, 2):
                assert sess.submit(uid, SYSTEM + TAILS[uid], 8) != "shed"
            _drain(sess, out, clock)
            for uid in (3, 4):
                assert sess.submit(uid, SYSTEM + TAILS[uid], 8) != "shed"
            _drain(sess, out, clock)
            outs[arm] = out
            stats[arm] = sess.stats()
        assert outs["on"] == outs["off"], "prefix cache changed outputs"
        assert set(outs["on"]) == {1, 2, 3, 4}
        assert all(len(v) == 8 for v in outs["on"].values())
        assert stats["on"]["prefix_hits"] >= 2
        assert stats["on"]["prefix_tokens_saved"] >= 32
        assert "prefix_hits" not in stats["off"]

    def test_requeued_stream_reprobes_the_cache(self, tiny):
        """Eviction with preempt_policy=requeue re-prefills through
        _activate, which probes the cache: the requeued stream's second
        prefill must be a hit. A completed seed wave pins SYSTEM first so
        the pins stay shared with the surviving stream (not reclaimable)
        while the victim is requeued. The pin cap is raised above the
        default num_blocks//2: decode blocks are offered too, and at cap 3
        their pins would LRU the SYSTEM entries out of the index."""
        model, params = tiny
        eng = _v2(model, params, num_blocks=7, block_size=8, max_context=40,
                  max_sequences=2)
        clock = FakeClock()
        sess = _mk_sess(eng, clock,
                        prefix={"enabled": True, "max_pinned_blocks": 6},
                        preempt_policy="requeue")
        pc = eng.prefix_cache
        assert sess.submit(9, SYSTEM + [99], 2) == "admitted"  # seed wave
        _drain(sess, clock=clock)
        out = {}
        # both map the 2 pinned SYSTEM blocks + want 3 novel blocks each:
        # 2 + 3 + 3 = 8 > 7 — the pool must preempt one mid-decode
        for uid in (1, 2):
            assert sess.submit(uid, SYSTEM + TAILS[uid], 20) != "shed"
        events = _drain(sess, out, clock)
        evicted = [e for e in events if e.kind == "evict"]
        assert evicted, "7-block pool must preempt one of the streams"
        assert pc.counters["hits"] >= 3, \
            "2 admission hits + the requeue re-prefill hit"
        want = {u: greedy(model, params, SYSTEM + TAILS[u], 20)
                for u in out}
        assert out == want

    def test_replay_shares_blocks_and_survives_donor_evict(self, tiny):
        """The satellite-2 regression: crash replay re-prefills through the
        cache (shares blocks with a LIVE stream), the donor then evicts,
        and the replayed stream still reconstructs the exact pre-crash
        greedy continuation."""
        model, params = tiny
        base = {u: greedy(model, params, SYSTEM + TAILS[u], 8)
                for u in (1, 3)}
        eng = _v2(model, params)
        clock = FakeClock()
        sess = _mk_sess(eng, clock, prefix=True)
        pc = eng.prefix_cache
        # live donor mid-decode: holds the committed SYSTEM blocks
        assert sess.submit(1, SYSTEM + TAILS[1], 8) == "admitted"
        for _ in range(3):
            clock.advance(0.05)
            sess.step()
        hits0 = pc.counters["hits"]
        # crash replay of uid 3 from a 2-token watermark: _activate maps
        # the SYSTEM blocks the donor committed
        assert sess.replay(3, SYSTEM + TAILS[3], 8,
                           emitted_tokens=base[3][:2]) == "replayed"
        clock.advance(0.05)
        sess.step()  # replayed stream prefills (novel tail only)
        assert pc.counters["hits"] == hits0 + 1
        d3 = eng.seqs[3]
        assert d3.cached_prefix_len == 16
        shared = list(d3.blocks[:2])
        assert all(eng.allocator.refcount(b) >= 2 for b in shared)
        # donor evicts mid-flight — refcounted release, sharer unaffected
        sess._evict(1, clock(), [])
        out = {}
        _drain(sess, out, clock)
        assert base[3][:2] + out[3] == base[3], \
            "replayed stream diverged after the donor evicted"

    def test_admission_gate_prices_cached_prefix(self, tiny):
        """TTFT projection charges n_prefill − cached: a prompt whose TTFT
        SLA only clears when the SYSTEM prefix is cached must be shed cold
        and admitted warm."""
        model, params = tiny
        clock = FakeClock()
        eng = _v2(model, params)
        cap = CapacityModel(prefill_tok_s=40.0, decode_step_s=1e-4)
        sess = ServingSession(
            eng, ServingPolicyConfig(prefix_cache={"enabled": True},
                                     admission="sla"),
            clock=clock, capacity=cap)
        # 17 novel tokens at 40 tok/s ≈ 0.43 s > 0.3 s TTFT → shed cold
        assert sess.submit(7, SYSTEM + [1], 2, ttft_sla_s=0.3) == "shed"
        # seed the cache (generous SLA), drain
        assert sess.submit(1, SYSTEM + [2], 2, ttft_sla_s=60.0) == "admitted"
        _drain(sess, clock=clock)
        # warm: 1 novel token ≈ 0.025 s < 0.3 s → admitted
        assert sess.submit(8, SYSTEM + [3], 2, ttft_sla_s=0.3) == "admitted"
        _drain(sess, clock=clock)

    def test_summary_events_and_strict_registry(self, tiny):
        from deepspeedsyclsupport_tpu.monitor.telemetry import EVENT_NAMES

        assert set(SERVE_PREFIX) <= set(EVENT_NAMES)
        model, params = tiny
        eng = _v2(model, params)
        clock = FakeClock()
        sess = _mk_sess(eng, clock, prefix=True)
        for uid in (1, 2):
            assert sess.submit(uid, SYSTEM + TAILS[uid], 3) == "admitted"
        _drain(sess, clock=clock)
        names = {e[0] for e in sess.summary_events(step=1)}
        assert set(SERVE_PREFIX) <= names
        ps = sess.prefix_stats()
        assert ps is not None and 0.0 <= ps["hit_ratio"] <= 1.0
        assert ps["pinned_blocks"] == eng.prefix_cache.pinned_blocks
