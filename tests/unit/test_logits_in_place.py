"""The drained sequences' logits stay in their forward's ``[max_sequences,
V]`` array (``ragged.LogitsRef``): a serving round samples all of them with
ONE fixed-shape program (``InferenceEngineV2.sample_drained``), whatever the
number of live sequences, and a ``[V]`` row is cut out only for a caller
that reads one (``put()``'s result, ``query()``), which the engine counts
(``logit_rows_sliced``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.sampling import (SamplingParams,
                                                         sample_token_dyn)
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.models import build_model

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"  # benchmark/run.py's
S_MAX = 6


@pytest.fixture(scope="module")
def tiny():
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def _v2(tiny, **kw):
    model, params = tiny
    return InferenceEngineV2(model, params, dtype=jnp.float32, **{
        "block_size": 8, "max_context": 64, "max_tokens_per_batch": 16,
        "max_sequences": 4, **kw})


@pytest.fixture(scope="module")
def compiles():
    """Every backend compile of this process from here on, as a list that a
    test clears and reads (the listener cannot be taken off again)."""
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _dur, **_kw: seen.append(name)
        if name == COMPILE_EVENT else None)
    return seen


def _row_of(model, params, context):
    """The dense model's last-position logits over ``context``."""
    return np.asarray(model.apply(
        params, jnp.asarray([context], jnp.int32))[0, -1])


def _serve(eng, requests, per_round=None, spy=None):
    """``requests`` [(uid, prompt, budget)] through a fresh session, all at
    once or ``per_round`` a round; {uid: tokens}. ``spy(session)`` runs
    after every round."""
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    todo, out = list(requests), {}
    for _ in range(400):
        for uid, prompt, budget in (todo if per_round is None
                                    else todo[:per_round]):
            assert sess.submit(uid, prompt, budget) == "admitted"
        todo = [] if per_round is None else todo[per_round:]
        if sess.idle and not todo:
            break
        for ev in sess.step():
            if ev.kind == "token":
                out.setdefault(ev.uid, []).extend(ev.tokens)
        if spy is not None:
            spy(sess)
    assert sess.idle and not todo
    sess.close()
    return out


# ------------------------------------------------- (a) the serving session
def test_a_session_at_every_live_count_slices_and_compiles_nothing(
        tiny, compiles):
    """One request a round until ``max_sequences`` are live, short prompts
    and prompts chunked over two forwards by turns, with budgets that keep
    all live together and end one by one: the live count climbs through
    mixed rounds and comes down through every count. After ``warmup()`` no
    program is new at any count, no row is cut out, and every request's
    greedy tokens are those it gets when served alone."""
    eng = _v2(tiny, max_sequences=S_MAX, num_blocks=64)
    eng.warmup()
    rng = np.random.default_rng(0)
    requests = [(uid, rng.integers(1, 250, 20 if uid % 2 else 3).tolist(),
                 S_MAX + 5 + uid) for uid in range(S_MAX)]
    slots, counts = {}, []

    def spy(sess):
        for uid, d in eng.seqs.items():
            if d.last_logits is not None:
                slots.setdefault(uid, set()).add(d.last_logits.slot)
        counts.append(sum(eng.has_logits(u) for u in sess.running))

    del compiles[:]
    sliced0 = eng.logit_rows_sliced
    together = _serve(eng, requests, per_round=1, spy=spy)
    assert compiles == []
    assert eng.logit_rows_sliced == sliced0
    assert set(range(1, S_MAX + 1)) <= set(counts)
    # a sequence's slot is its place in ONE forward's chunks: it moved
    assert any(len(seen) > 1 for seen in slots.values()), slots
    for uid, prompt, budget in requests:
        alone = _serve(eng, [(uid, prompt, budget)])
        assert together[uid] == alone[uid], uid
        assert len(alone[uid]) == budget
    assert compiles == [] and eng.logit_rows_sliced == sliced0
    assert eng.allocator.free_blocks == eng.config.num_blocks


def test_a_round_is_two_dispatches_at_any_live_count(tiny):
    """The forward and the sampler: ``host_dispatches`` grows by at most 2 a
    round however many sequences are live."""
    eng = _v2(tiny, max_sequences=S_MAX, num_blocks=64)
    eng.warmup()
    seen = [eng.host_dispatches]
    _serve(eng, [(uid, [uid + 1, 7, 9], 6) for uid in range(S_MAX)],
           spy=lambda _sess: seen.append(eng.host_dispatches))
    steps = np.diff(seen)
    assert steps.max() == 2 and steps[1:-1].min() == 2, steps


# --------------------------------------------- (b) put() and query() rows
def test_put_and_query_hand_out_rows_cut_on_demand(tiny):
    model, params = tiny
    eng = _v2(tiny)
    p1, p2 = [5, 9, 2, 7], [11, 3, 8]
    out1 = eng.put([1], [p1])
    assert eng.logit_rows_sliced == 0
    assert 1 in out1 and list(out1) == [1] and len(out1) == 1
    assert eng.has_logits(1) and not eng.has_logits(2)
    assert eng.logit_rows_sliced == 0      # membership launches nothing
    row1 = out1[1]
    assert row1.shape == (model.config.vocab_size,)
    assert eng.logit_rows_sliced == 1
    out1[1], dict(out1.items())            # a result cuts its row once
    assert eng.logit_rows_sliced == 1
    np.testing.assert_allclose(row1, _row_of(model, params, p1),
                               rtol=2e-4, atol=2e-4)
    # two more forwards (uid 2's prompt, then its decode step): uid 1's row
    # is kept from the EARLIER forward, in slot 0 of that one
    out2 = eng.put([2], [p2])
    tok = int(np.argmax(out2[2]))
    out3 = eng.put([2], [[tok]])
    assert 1 not in out3 and set(out3) == {2}
    assert eng.seqs[1].last_logits.array is not eng.seqs[2].last_logits.array
    sliced = eng.logit_rows_sliced
    np.testing.assert_array_equal(eng.query(1), row1)
    np.testing.assert_array_equal(eng.query(2), out3[2])
    assert eng.logit_rows_sliced == sliced + 3   # query cuts at every call
    np.testing.assert_allclose(eng.query(2),
                               _row_of(model, params, p2 + [tok]),
                               rtol=2e-4, atol=2e-4)
    assert eng.query(99) is None
    # new input: the old row is gone until the input has drained
    eng.put([1], [[4, 4]], drain=False)
    assert eng.has_logits(1)
    eng.seqs[1].pending.append(3)
    eng.seqs[1].last_logits = None
    assert eng.query(1) is None and not eng.has_logits(1)


@pytest.mark.parametrize("order", [(1, 2, 3), (3, 1, 2)])
def test_slots_follow_the_forward_not_the_uid(tiny, order):
    """Three sequences decoded in one forward in ``order``: each gets its
    own row, whichever slot it had."""
    model, params = tiny
    eng = _v2(tiny)
    prompts = {1: [5, 9, 2], 2: [11, 3, 8, 6], 3: [7]}
    eng.put(list(order), [prompts[u] for u in order])
    out = eng.put([1, 2, 3], [[21], [22], [23]])
    # the scheduler serves equals in the order they came in
    assert [eng.seqs[u].last_logits.slot for u in order] == [0, 1, 2]
    for u in order:
        np.testing.assert_allclose(
            out[u], _row_of(model, params, prompts[u] + [20 + u]),
            rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ (c) sample_drained
def _three_live(tiny):
    """uids 1-3 drained, uid 1 by an EARLIER forward than 2 and 3."""
    eng = _v2(tiny)
    eng.put([1], [[5, 9, 2, 7]])
    eng.put([3, 2], [[11, 3, 8], [6, 1]])
    return eng


def test_greedy_is_each_row_s_argmax_whoever_else_is_live(tiny):
    eng = _three_live(tiny)
    want = {u: int(np.argmax(eng.query(u))) for u in (1, 2, 3)}
    key = jax.random.PRNGKey(0)
    sliced, sent = eng.logit_rows_sliced, eng.host_dispatches
    for uids in [(1,), (2,), (3, 2), (2, 3), (1, 2, 3), (3, 1)]:
        toks, tail = eng.sample_drained(uids, key, SamplingParams())
        assert tail is None and toks.dtype == np.int32
        assert toks.tolist() == [want[u] for u in uids], uids
    assert eng.logit_rows_sliced == sliced     # gathered, never cut out
    # one launch per forward that holds a row: 1, 1, 1, 1, 2, 2
    assert eng.host_dispatches - sent == 8


@pytest.mark.parametrize("sp", [
    SamplingParams(True, 0.8, 0, 1.0), SamplingParams(True, 1.3, 5, 1.0),
    SamplingParams(True, 1.0, 0, 0.9)], ids=["temperature", "top_k", "top_p"])
def test_a_draw_follows_the_key_the_place_and_the_row_alone(tiny, sp):
    """With ``do_sample`` a fixed key gives the same tokens again, and the
    token at place ``i`` is what ONE ``[max_sequences, V]`` call draws for
    row ``i``: also for rows that two forwards hold (two launches)."""
    eng = _three_live(tiny)
    uids = (2, 1, 3)
    key = jax.random.PRNGKey(7)
    first, _ = eng.sample_drained(uids, key, sp)
    again, _ = eng.sample_drained(uids, key, sp)
    assert first.tolist() == again.tolist()
    rows = jnp.zeros((eng.config.max_sequences,
                      eng.model.config.vocab_size), jnp.float32)
    for i, u in enumerate(uids):
        rows = rows.at[i].set(eng.query(u))
    want = sample_token_dyn(rows, key, jnp.float32(sp.temperature),
                            jnp.float32(sp.top_p), sp.structure)
    assert first.tolist() == np.asarray(want)[:3].tolist()
    assert any(eng.sample_drained(uids, jax.random.PRNGKey(k), sp)[0].tolist()
               != first.tolist() for k in range(8, 14))   # a draw, not argmax


def test_a_sampling_session_repeats_itself_from_its_key(tiny):
    eng = _v2(tiny)
    eng.warmup()
    runs = []
    for _ in range(2):
        sess = ServingSession(
            eng, ServingPolicyConfig(admission="none"),
            sampling=SamplingParams(True, 0.9, 0, 0.95),
            rng=jax.random.PRNGKey(3))
        out = {}
        for uid in (1, 2, 3):
            sess.submit(uid, [uid, 5, 9], 6)
        while not sess.idle:
            for ev in sess.step():
                if ev.kind == "token":
                    out.setdefault(ev.uid, []).extend(ev.tokens)
        sess.close()
        runs.append(out)
    assert runs[0] == runs[1] and all(len(v) == 6 for v in runs[0].values())
    assert eng.logit_rows_sliced == 0
