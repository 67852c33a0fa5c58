"""A per-token round launches the next forward BEFORE it reads the sampled
tokens back (``ServingSession._per_token_round``): the forward takes its
decode rows' tokens from the sampler's output on the device
(``engine_v2.SampledTokens``, ``model._tokens_in``). What the caller sees
must not change: for the same requests, seed and arrival rounds the session
hands out the same tokens in the same ``step()`` as the round written in
order: sample, read the values, THEN put them (``Reference`` below, built
from ``put(..., drain=True)``, ``query`` and the same sampler keys). A real
session on the CPU sim, tiny dense and sparse models, float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.sampling import (SamplingParams,
                                                         sample_token_dyn,
                                                         split_key)
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (device_token,
                                                          split_device_tokens)
from deepspeedsyclsupport_tpu.models import build_model

S = 3            # max_sequences: a fourth request waits for a freed slot
SEED = 11


@pytest.fixture(scope="module", params=["tiny", "tiny-moe"])
def built(request):
    model = build_model(request.param, dtype="float32")
    return model, model.init_params()


def _engine(built, **kw):
    model, params = built
    return InferenceEngineV2(model, params, dtype=jnp.float32, seed=SEED,
                             **{"block_size": 8, "max_context": 64,
                                "max_tokens_per_batch": 48,
                                "max_sequences": S, **kw})


def _said(events):
    return [(e.kind, e.uid, list(e.tokens), e.reason) for e in events]


class Reference:
    """The round in order, on an engine of its own: one sampler call over
    the drained rows (``query``; row ``i`` of ``[max_sequences, V]`` is the
    ``i``-th drained stream's, as the engine's sampler lays them), the
    values read, the streams whose budget, context or EOS ends closed, then
    ONE ``put`` of the values and the new prompts."""

    def __init__(self, eng, sampling=None, eos=None):
        self.eng, self.sp, self.eos = eng, sampling or SamplingParams(), eos
        self.rng = jax.random.PRNGKey(eng.config.seed + 1)
        self.budget, self.fresh, self.pending = {}, {}, {}

    def submit(self, uid, prompt, budget):
        self.budget[uid] = budget
        self.fresh[uid] = list(prompt)

    def step(self):
        eng, said = self.eng, []
        drained = [u for u in self.budget
                   if u not in self.pending and eng.has_logits(u)]
        if drained:
            self.rng, sub = split_key(self.rng)
            rows = jnp.stack([eng.query(u) for u in drained])
            rows = jnp.concatenate([rows, jnp.zeros(
                (eng.config.max_sequences - len(drained), rows.shape[1]),
                rows.dtype)])
            toks = np.asarray(sample_token_dyn(
                rows, sub, np.float32(self.sp.temperature),
                np.float32(self.sp.top_p), self.sp.structure))
            for u, tok in zip(drained, toks.tolist()):
                said.append(("token", u, [tok], ""))
                self.budget[u] -= 1
                reason = ("eos" if tok == self.eos
                          else "done" if self.budget[u] <= 0
                          else "context" if eng.seqs[u].n_cached
                          >= eng.config.max_context else "")
                if reason:
                    said.append(("finish", u, [], reason))
                    del self.budget[u]
                    eng.flush([u])
                else:
                    self.pending[u] = tok
        uids = list(self.pending) + list(self.fresh)
        if uids:
            got = eng.put(uids, [[self.pending[u]] for u in self.pending]
                          + list(self.fresh.values()), drain=True)
            for u in got.admission.admitted:
                self.pending.pop(u, None)
                self.fresh.pop(u, None)
        return said


def _drive(built, requests, late=(), sampling=None, eos=None, policy=None,
           rounds=60, **engine):
    """The same arrivals through a session and through the reference:
    ``requests`` before the first round, each of ``late`` the round after a
    slot was freed (as a closed loop's caller sends its next). Returns the
    session, both lists of per-round events, and the session's records."""
    sess = ServingSession(
        _engine(built, **engine),
        policy or ServingPolicyConfig(admission="none"),
        sampling=sampling, eos_token_id=eos)
    ref = Reference(_engine(built, **engine), sampling, eos)
    for uid, prompt, budget in requests:
        assert sess.submit(uid, prompt, budget) == "admitted"
        ref.submit(uid, prompt, budget)
    late = list(late)
    got, want = [], []
    for _ in range(rounds):
        if sess.idle and not late:
            break
        got.append(_said(sess.step()))
        want.append(ref.step())
        if late and any(k == "finish" for k, *_ in got[-1]):
            uid, prompt, budget = late.pop(0)
            assert sess.submit(uid, prompt, budget) == "admitted"
            ref.submit(uid, prompt, budget)
    assert sess.idle and not late
    return sess, got, want


def _streams(rounds):
    out = {}
    for said in rounds:
        for kind, uid, toks, _reason in said:
            if kind == "token":
                out.setdefault(uid, []).extend(toks)
    return out


def _rounds(sess):
    return [r["data"] for r in sess.drain_trace()
            if r["data"].get("stage") == "round"]


REQUESTS = [(1, [3, 1, 4, 1, 5], 7), (2, list(range(20, 33)), 4),
            (3, [9, 2, 6], 9)]
LATE = [(4, [5, 3, 5, 8, 9, 7], 5)]


# ------------------------------------------- (i)-(iii): the same, in order
@pytest.mark.parametrize("sampling", [
    None, SamplingParams(True, 0.8, 0, 0.9)], ids=["greedy", "top_p"])
def test_the_session_says_what_the_round_in_order_says(built, sampling):
    """Budgets that end in different rounds, a fourth request in the freed
    slot: every ``step()`` returns the reference's events, token for token,
    and every forward after the first was launched ahead of a read-back."""
    sess, got, want = _drive(built, REQUESTS, LATE, sampling=sampling)
    assert got == want
    assert {u: len(t) for u, t in _streams(got).items()} == {
        1: 7, 2: 4, 3: 9, 4: 5}
    if sampling is not None:    # ... and the draw is a draw, not an argmax
        greedy = _drive(built, REQUESTS, LATE)[1]
        assert _streams(greedy) != _streams(got)
    stats = sess.stats()
    assert stats["speculative_rows"] == 0
    launched = [d for d in _rounds(sess) if d["program"]]
    assert [d["ahead"] for d in launched] == [0] + [1] * (len(launched) - 1)
    assert stats["launched_ahead"] == len(launched) - 1
    assert sess.eng.allocator.free_blocks == sess.eng.config.num_blocks
    assert sess.eng.logit_rows_sliced == 0
    sess.close()


# ---------------------------------------------------------- (iv): an EOS
def test_an_eos_is_learnt_one_forward_late_and_costs_one_row(built):
    """The token stream 1 emits third is made the EOS: the host learns of it
    at the read-back, when the next forward already holds a row for the
    stream. That row is the one speculative row; nothing is emitted after
    the EOS, the stream's blocks are back, and the others' streams are what
    they are without an EOS."""
    plain = _streams(_drive(built, REQUESTS)[1])
    eos = plain[1][2]
    # the greedy stream must not have said that token earlier, nor may the
    # neighbours say it at all (they would end too)
    if eos in plain[1][:2] or any(eos in plain[u] for u in (2, 3)):
        pytest.skip("the tiny model repeats its third token")
    sess, got, want = _drive(built, REQUESTS, eos=eos)
    assert got == want
    streams = _streams(got)
    assert streams[1] == plain[1][:3]
    assert streams[2] == plain[2] and streams[3] == plain[3]
    assert ("finish", 1, [], "eos") in [e for said in got for e in said]
    assert sess.stats()["speculative_rows"] == 1
    spec = [d for d in _rounds(sess) if d["spec_rows"]]
    assert len(spec) == 1 and spec[0]["spec_rows"] == 1
    # the round that learnt of it had launched a row for the stream
    assert spec[0]["n_seqs"] == 3 and 1 in spec[0]["uids"]
    assert 1 not in sess.eng.seqs
    assert sess.eng.allocator.free_blocks == sess.eng.config.num_blocks
    sess.close()


def test_an_eos_that_is_also_the_last_token_costs_no_row(built):
    """A stream whose budget ends with the token is known to end: it gets no
    row, EOS or not, and closes as the reference closes it (``eos``)."""
    plain = _streams(_drive(built, REQUESTS)[1])
    eos = plain[2][3]           # stream 2's fourth and last token
    if eos in plain[2][:3] or any(eos in plain[u] for u in (1, 3)):
        pytest.skip("the tiny model repeats that token")
    sess, got, want = _drive(built, REQUESTS, eos=eos)
    assert got == want
    assert ("finish", 2, [], "eos") in [e for said in got for e in said]
    assert sess.stats()["speculative_rows"] == 0
    sess.close()


# --------------------------------------------------- (v): KV pressure
def test_an_evicted_stream_is_requeued_and_says_every_token(built):
    """A pool too small for three growing streams: one is evicted while its
    last sampled token is still on the device, requeued with that token in
    its context, prefilled again, and finishes its budget. Greedy streams do
    not depend on who was evicted when: they equal the roomy run's. The
    victim's token is said BEFORE its eviction, in the same ``step()``."""
    requests = [(1, list(range(1, 8)), 12), (2, list(range(10, 17)), 12),
                (3, list(range(20, 27)), 12)]
    roomy = _streams(_drive(built, requests)[1])
    sess = ServingSession(
        _engine(built, num_blocks=5),
        ServingPolicyConfig(admission="none", preempt_policy="requeue"))
    for uid, prompt, budget in requests:
        sess.submit(uid, prompt, budget)
    got = []
    for _ in range(200):
        if sess.idle:
            break
        got.append(_said(sess.step()))
    assert sess.idle
    assert sess.counters["evicted"] >= 1
    assert _streams(got) == roomy
    for said in got:
        for k, (kind, uid, *_rest) in enumerate(said):
            if kind == "evict":
                assert ("token", uid) in [(e[0], e[1]) for e in said[:k]]
    assert sess.eng.allocator.free_blocks == 5
    assert sess.stats()["speculative_rows"] == 0
    sess.close()


# -------------------------------------------------- (vi): the prefix cache
def test_with_a_prefix_cache_history_is_whole_after_every_round(built):
    """``history`` (what the prefix index hashes) gets a decode token's
    VALUE at the read-back, not at the put: after every ``step()`` it is the
    stream's context so far, and a second request over the first one's
    prompt adopts its full blocks and says what it says without a cache."""
    prompt = list(range(30, 50))                 # two full blocks and a half
    requests = [(1, prompt, 10)]
    late = [(2, prompt[:16] + [7, 7, 7], 6)]
    _s, plain, _w = _drive(built, requests, late)
    sess = ServingSession(_engine(built), ServingPolicyConfig(
        admission="none", prefix_cache={"enabled": True}))
    asked = {u: p for u, p, _b in requests + late}
    sess.submit(*requests[0])
    got, late = [], list(late)
    for _ in range(60):
        if sess.idle and not late:
            break
        got.append(_said(sess.step()))
        said = _streams(got)
        for uid, d in sess.eng.seqs.items():
            context = asked[uid] + said.get(uid, [])
            assert d.history == context[:d.n_cached], (uid, len(got))
            assert len(d.block_hashes) == d.n_cached // 8
        if late and any(k == "finish" for k, *_ in got[-1]):
            sess.submit(*late.pop(0))
    assert sess.idle
    assert got == plain
    assert sess.prefix_stats()["hits"] == 1
    assert sess.prefix_stats()["tokens_saved"] == 16
    sess.close()


# ------------------------------------------------------ the order is real
def test_the_forward_is_launched_before_the_tokens_are_read(built):
    """On a clock that ticks once a reading: in every round that sampled and
    launched, ``launch_t`` (read the instant ``_dispatch`` returned) lies
    before the engine was asked for the sampled tokens, the record says
    ``ahead`` and ``stats()`` counts it. The first round after idle has
    nothing to read and launches nothing ahead."""
    ticks = [0]

    def clock():
        ticks[0] += 1
        return float(ticks[0])

    eng = _engine(built)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"),
                          clock=clock)
    asked_at = {}
    read = eng.read_sampled

    def read_sampled(sampled):
        asked_at[sess._round] = ticks[0]
        return read(sampled)

    eng.read_sampled = read_sampled
    for uid, prompt, budget in REQUESTS:
        sess.submit(uid, prompt, budget)
    for _ in range(40):
        if sess.idle:
            break
        sess.step()
    assert sess.idle
    rounds = _rounds(sess)
    assert rounds[0]["ahead"] == 0 and 1 not in asked_at
    ahead = [d for d in rounds if d["ahead"]]
    assert len(ahead) == len([d for d in rounds if d["program"]]) - 1
    for d in ahead:
        assert d["t0"] < d["launch_t"] <= asked_at[d["round"]] < d["t1"]
        assert d["phases"]["readback"] > 0
    # the last round sampled, closed every stream and launched nothing
    assert rounds[-1]["program"] is None and rounds[-1]["uids"]
    assert sess.stats()["launched_ahead"] == len(ahead)
    # then idle again: the next request's first round is in order again
    sess.submit(9, [1, 2, 3], 2)
    sess.step()
    assert _rounds(sess)[-1]["ahead"] == 0
    while not sess.idle:
        sess.step()
    sess.close()


# ------------------------------------------------- the engine's own surface
def test_a_token_by_reference_gives_the_logits_of_its_value(built):
    """``put(..., sampled=)`` with ``sampled.ref(uid)`` against ``put`` with
    the value read first: the same logits from both forward programs, the
    value in ``pending`` for a reference no forward ate, and an error for a
    reference without the launch that holds it."""
    key = jax.random.PRNGKey(5)
    logits = {}
    for how in ("value", "reference"):
        eng = _engine(built)
        eng.put([1, 2], [[3, 1, 4], [9, 2, 6, 5]])
        for mixed in (False, True):      # decode_forward, then ragged_forward
            sampled = eng.sample_launch([2, 1], key, SamplingParams())
            if how == "value":
                toks, _ = eng.read_sampled(sampled)
                decode, sampled = [[int(toks[1])], [int(toks[0])]], None
            else:
                decode = [[sampled.ref(1)], [sampled.ref(2)]]
                assert decode == [[device_token(1)], [device_token(0)]]
            uids, new = ([1, 2, 7], decode + [[8, 8, 8, 8]]) if mixed \
                else ([1, 2], decode)
            out = eng.put(uids, new, drain=False, sampled=sampled)
            if sampled is not None:
                assert sampled.taken == {1, 2}
                eng.read_sampled(sampled)
            logits[how, mixed] = np.stack([np.asarray(out[u])
                                           for u in uids])
    for mixed in (False, True):
        np.testing.assert_array_equal(logits["value", mixed],
                                      logits["reference", mixed])
    # a reference nobody ate: the read-back writes the value over it
    eng = _engine(built)
    eng.put([1], [[3, 1, 4]])
    sampled = eng.sample_launch([1], key, SamplingParams())
    eng.seqs[1].pending.append(sampled.ref(1))
    toks, _ = eng.read_sampled(sampled)
    assert eng.seqs[1].pending == [int(toks[0])] and not sampled.taken
    with pytest.raises(ValueError, match="sampled="):
        eng.put([1], [[device_token(0)]])
    tokens, take_from = split_device_tokens(
        np.array([5, device_token(2), 0, device_token(0)], np.int32))
    assert tokens.tolist() == [5, 0, 0, 0]
    assert take_from.tolist() == [-1, 2, -1, 0]
