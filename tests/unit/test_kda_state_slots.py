"""The third kind of recurrent state behind the engine's slots, at tiny sizes
on the CPU (one period of ``solar-open2``: a gated attention layer and three
delta-rule layers, an expert block after each): a state slot AND KV blocks a
live sequence; eviction under ``requeue`` gives both back and the stream that
is prefilled again says what a fresh one says; idle holds no slot and no
block; what a model with such state refuses, in ``_refuse_stateful``'s
sentence; what the ``round`` record carries for the readers."""
import jax
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2.config import ServingPolicyConfig
from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession
from tests.family_harness import Harness, engines  # noqa: F401

WIDTHS = dict(
    hidden_size=32, intermediate_size=48, num_layers=8,
    layer_pattern="*EKEKEKE", num_heads=4, num_kv_heads=2, head_dim=8,
    vocab_size=96, kda_num_heads=2, kda_head_dim=8, kda_gate_rank=8,
    kda_chunk_size=4, num_experts=8, num_experts_held=4,
    num_experts_per_tok=2, moe_intermediate_size=16, max_seq_len=128,
    dtype="float32", routed_write_share=None)
ENGINE = {"max_context": 32, "max_sequences": 3, "num_blocks": 6,
          "block_size": 8, "max_tokens_per_batch": 8,
          "prefill_attn": "xla", "decode_attn": "xla"}
ROOMY = {"num_blocks": 24}
PROMPTS = {1: [1, 2, 3], 2: [4, 5, 6, 7, 8], 3: [7, 8, 9]}
BUDGET = 18
H = Harness(None, ENGINE)
engine_of = H.engine_of


@pytest.fixture(scope="module")
def built():
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("solar-open2", **WIDTHS)
    model.seed = 11
    # (ONE program: a draw a leaf is one a shape otherwise)
    return model, jax.jit(model.init_params)()


def drive(sess, limit=600):
    """The session to idle -> ``(tokens by uid, evictions, the most state
    slots live at once)``."""
    out, evicted, live = {}, 0, 0
    for _ in range(limit):
        if sess.idle:
            break
        for e in sess.step():
            if e.kind == "token":
                out.setdefault(e.uid, []).extend(e.tokens)
            evicted += e.kind == "evict"
        live = max(live, sess.eng.state_stats()["slots_live"])
    assert sess.idle
    return out, evicted, live


@pytest.fixture(scope="module")
def fresh(built):
    """Each prompt's greedy tokens alone on an engine with room: what a
    stream says when nothing is ever taken from it. (The roomy engine the
    cases below are handed: ``engines(**ROOMY)``.)"""
    eng = H.idle_engine(*built, **ROOMY)
    return {uid: eng.generate([p], max_new_tokens=BUDGET)[0]
            for uid, p in PROMPTS.items()}


def test_eviction_and_requeue_give_back_the_slot_and_the_blocks(built, fresh):
    """A pool of 6 blocks under three streams that want 9: the session
    evicts, the evicted stream's slot and blocks go back, it is prefilled
    again from zeros (prompt + what it had said, through the chunked form)
    and every stream ends with the tokens a fresh one says."""
    eng = engine_of(*built)
    sess = ServingSession(eng, ServingPolicyConfig(preempt_policy="requeue"))
    for uid, p in PROMPTS.items():
        assert sess.submit(uid, p, BUDGET) == "admitted"
    out, evicted, live = drive(sess)
    assert evicted > 0 and live == 3
    assert out == fresh
    # idle: no slot, no block, no descriptor
    assert eng.state_stats()["slots_live"] == 0 and not eng.seqs
    assert sorted(eng._state_free) == [0, 1, 2]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_admission_is_by_state_slots_and_by_blocks(engines, fresh):
    """Five requests on three slots: two wait for a slot, nobody is shed,
    never more than three slots live, and all five finish."""
    eng = engines(**ROOMY)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    for uid in range(5):
        sess.submit(uid, PROMPTS[1 + uid % 3], BUDGET)
    out, evicted, live = drive(sess)
    assert (evicted, live) == (0, 3)
    assert [out[uid] for uid in range(5)] == [
        fresh[1 + uid % 3] for uid in range(5)]
    with pytest.raises(RuntimeError, match="no recurrent-state slot free"):
        for uid in range(10, 14):
            eng._new_seq(uid)
    eng.flush([10, 11, 12])
    assert eng.state_stats()["slots_live"] == 0


def test_the_round_record_carries_the_delta_rule_counts(engines):
    """``kda_rows`` / ``kda_pieces`` / ``kda_first`` where a Mamba model's
    record has ``ssm_*``: rows through each of the three layers, pieces
    summed over them (a one-token chunk one piece, a longer one a piece
    every 4 rows), those that start a sequence."""
    eng = engines(**ROOMY)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(0, list(range(1, 11)), 3)            # 10 rows: 8 + 2
    drive(sess)
    rounds = [r["data"] for r in sess.drain_trace()
              if r.get("name") == "serve/stage"
              and r["data"].get("stage") == "round" and r["data"]["program"]]
    first = rounds[0]
    assert (first["kda_rows"], first["kda_pieces"], first["kda_first"]) \
        == (8, 3 * 2, 3)
    assert (rounds[1]["kda_rows"], rounds[1]["kda_pieces"],
            rounds[1]["kda_first"]) == (2, 3, 0)
    decode = [r for r in rounds if r["program"] == "decode_forward"]
    assert decode and all(
        (r["kda_rows"], r["kda_pieces"], r["kda_first"]) == (1, 3, 0)
        for r in decode)
    assert not any(k.startswith(("ssm_", "ret_")) for r in rounds for k in r)


def test_what_is_refused_says_the_stateful_sentence(built, tmp_path):
    eng = engine_of(*built)
    sentence = ("is not available for a model with recurrent state "
                r"\(ModelConfig.state_layers: Mamba-2 or power-retention "
                r"layers, or delta-rule ones\): it would need ")
    with pytest.raises(NotImplementedError, match=sentence + "a snapshot of "
                       "the recurrent state at every shared block boundary"):
        eng.install_prefix_cache()
    with pytest.raises(NotImplementedError, match=r"serialize\(\) "
                       + sentence + "a snapshot of the recurrent state "
                       "beside the parameters"):
        eng.serialize(str(tmp_path / "snap"))
    stats = eng.state_stats()
    assert (stats["layers"], stats["slots"], stats["dtype"]) \
        == (3, 3, "float32")
    assert stats["bytes_per_slot"] == 3 * (2 * 8 * 8 * 4 + 3 * 48 * 4)


def test_a_requeued_streams_logits_are_a_fresh_ones(engines):
    """The same through ``put()``: a sequence flushed mid-stream and fed
    again whole (what ``requeue`` does) gives the logits of one that was
    never interrupted, from whatever slot it is handed."""
    eng = engines(**ROOMY)
    prompt, said = list(range(20, 31)), [3, 14, 15]
    whole = np.asarray(eng.put([1], [prompt + said])[1])
    eng.flush([1])
    # interrupted: the prompt, two tokens, then evicted and prefilled again
    eng.put([2], [prompt])
    eng.put([2], [said[:1]])
    other = eng.put([3], [[5, 6, 7]])                 # takes another slot
    assert 3 in other
    eng.flush([2])
    again = np.asarray(eng.put([4], [prompt + said])[4])
    np.testing.assert_allclose(again, whole, atol=1e-5)
    eng.flush([3, 4])
    assert eng.state_stats()["slots_live"] == 0
