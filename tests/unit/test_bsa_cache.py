"""The pooled keys beside the pages and the fourth kind of recurrent state
behind the engine's slots, at tiny sizes on the CPU (one period of
``minicpm-sala``: a block-sparse attention layer and three lightning layers,
a dense MLP after each): a state slot AND KV pages a live sequence, the
pooled keys AT the pages; eviction under ``requeue`` gives all back and the
stream that is prefilled again (pooled keys and state from zeros, into pages
another stream left its keys in) says what a fresh one says; idle holds no
slot and no page; what such a model refuses, in ``_refuse_stateful``'s
sentence; what the ``round`` record carries for the readers."""
import jax
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2.config import ServingPolicyConfig
from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession
from tests.family_harness import Harness, engines  # noqa: F401

WIDTHS = dict(
    hidden_size=32, intermediate_size=48, num_layers=8,
    layer_pattern="*FLFLFLF", num_heads=4, num_kv_heads=2, head_dim=8,
    vocab_size=96, lightning_heads=2, lightning_head_dim=8,
    lightning_chunk_size=4, sparse_block_topk=4, sparse_block_size=4,
    sparse_block_kernel=2, sparse_block_stride=1, sparse_block_init=1,
    sparse_block_window=2, sparse_block_dense_len=20, max_seq_len=128,
    dtype="float32")
ENGINE = {"max_context": 48, "max_sequences": 3, "num_blocks": 20,
          "block_size": 4, "max_tokens_per_batch": 8,
          "prefill_attn": "xla", "decode_attn": "xla"}
ROOMY = {"num_blocks": 48}
# (prompts past dense_len 20 = 5 blocks, of which 4 are read)
PROMPTS = {1: list(range(1, 23)), 2: list(range(30, 55)), 3: [7, 8, 9]}
BUDGET = 14
H = Harness(None, ENGINE)
engine_of = H.engine_of


@pytest.fixture(scope="module")
def built():
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("minicpm-sala", **WIDTHS)
    model.seed = 11
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
    """Each prompt's greedy tokens alone on an engine with room."""
    eng = H.idle_engine(*built, **ROOMY)
    return {uid: eng.generate([p], max_new_tokens=BUDGET)[0]
            for uid, p in PROMPTS.items()}


def test_eviction_and_requeue_give_back_slot_pages_and_pooled_keys(built,
                                                                   fresh):
    """A pool of 20 pages under three streams that want 24: the session
    evicts, the evicted stream's slot and pages go back (its pooled keys
    with them: they lie AT the pages), it is prefilled again from zeros into
    whatever pages it is handed, and every stream ends with the tokens a
    fresh one says."""
    eng = engine_of(*built)
    sess = ServingSession(eng, ServingPolicyConfig(preempt_policy="requeue"))
    for uid, p in PROMPTS.items():
        assert sess.submit(uid, p, BUDGET) == "admitted"
    out, evicted, live = drive(sess)
    assert evicted > 0 and live == 3
    assert out == fresh
    # idle: no slot, no page, no descriptor
    assert eng.state_stats()["slots_live"] == 0 and not eng.seqs
    assert sorted(eng._state_free) == [0, 1, 2]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_admission_is_by_state_slots_and_by_pages(engines, fresh):
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


def test_the_round_record_carries_the_counts_of_both_mixers(engines):
    """``la_rows`` / ``la_pieces`` / ``la_first`` where a Mamba model's
    record has ``ssm_*`` (rows through each of the three layers, pieces
    summed over them, those that start a sequence), counted by the host
    before the launch; and ``bsa.COUNTS``, counted on the DEVICE and so on
    the record after the forward's own."""
    from deepspeedsyclsupport_tpu.inference.v2.bsa import COUNTS

    eng = engines(**ROOMY)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(0, list(range(1, 11)), 3)            # 10 rows: 8 + 2
    drive(sess)
    rounds = [r["data"] for r in sess.drain_trace()
              if r.get("name") == "serve/stage"
              and r["data"].get("stage") == "round" and r["data"]["program"]]
    first = rounds[0]
    assert (first["la_rows"], first["la_pieces"], first["la_first"]) \
        == (8, 3 * 2, 3)
    assert (rounds[1]["la_rows"], rounds[1]["la_pieces"],
            rounds[1]["la_first"]) == (2, 3, 0)
    decode = [r for r in rounds if r["program"] == "decode_forward"]
    assert decode and all(
        (r["la_rows"], r["la_pieces"], r["la_first"]) == (1, 3, 0)
        for r in decode)
    assert not any(k.startswith(("ssm_", "ret_", "kda_", "dsa_"))
                   for r in rounds for k in r)
    # the device's counts of a forward ride behind the NEXT round's tokens:
    # the first decode round's record says what the last prompt chunk read
    # (2 rows at positions 8 and 9 under dense_len: 3 blocks each, a tile a
    # row on this route), later ones what the decode step before them did
    counted = [r for r in rounds if "bsa_rows" in r]
    assert counted and set(COUNTS) <= set(counted[0])
    said = counted[0]
    assert (said["bsa_rows"], said["bsa_pages"], said["bsa_pairs"]) \
        == (2, 2 * 2 * 3, 2 * (9 + 10))
    assert counted[-1]["bsa_rows"] == 1 == counted[-1]["bsa_row_pages"] // 6


def test_what_is_refused_says_the_stateful_sentence(built, tmp_path):
    eng = engine_of(*built)
    sentence = ("is not available for a model with recurrent state "
                r"\(ModelConfig.state_layers: lightning, Mamba-2 or "
                r"power-retention layers, or delta-rule ones\): it would need ")
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
    assert stats["bytes_per_slot"] == 3 * 2 * 8 * 8 * 4


@pytest.mark.parametrize("uid", [1, 2])
def test_the_atoms_under_their_blocks_say_what_every_row_alone_says(
        built, engines, fresh, uid):
    """The whole route at the engine: chunks of 8 rows as atoms of 4 under
    the selection of BLOCKS a KV head (the ragged kernel interpreted: steps
    of 8 pages over tables of 12, so the last step lies half past the
    table's end and the contexts are no multiple of it; the grid's unused
    atoms dead) and the one-token rows over their own page tables say the
    greedy tokens of the engine that runs every row alone through
    ``jax.numpy``, prompts past ``dense_len`` where rows read 4 blocks of
    up to 7."""
    eng = engines(prefill_attn="kernel_interpret",
                  decode_attn="pallas_interpret", atom_q_size=4, **ROOMY)
    assert eng.generate([PROMPTS[uid]], max_new_tokens=BUDGET)[0] \
        == fresh[uid]


def test_a_requeued_streams_logits_are_a_fresh_ones(engines):
    """The same through ``put()``: a sequence flushed mid-stream and fed
    again whole (what ``requeue`` does) gives the logits of one that was
    never interrupted, from whatever slot and pages it is handed: pages in
    which ANOTHER stream's pooled keys still lie."""
    eng = engines(**ROOMY)
    prompt, said = list(range(20, 47)), [3, 14, 15]
    whole = np.asarray(eng.put([1], [prompt + said])[1])
    eng.flush([1])
    # interrupted: the prompt, two tokens, then evicted and prefilled again
    eng.put([2], [prompt])
    eng.put([2], [said[:1]])
    other = eng.put([3], [list(range(60, 90))])       # other pages, a slot
    assert 3 in other
    eng.flush([2])
    again = np.asarray(eng.put([4], [prompt + said])[4])
    np.testing.assert_allclose(again, whole, atol=1e-5)
    eng.flush([3, 4])
    assert eng.state_stats()["slots_live"] == 0
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
