"""A learned sparse-attention indexer on the serving path
(``ModelConfig.index_topk``; ``inference/v2/dsa.py``, ``ops/sparse_index.py``
and the ragged kernel's selection operand): the served logits against the
plain float32 reference of the family (``benchmark/families/KeyeVL2.py``:
independent of the code under test), the three kernels in interpret mode
against their ``jax.numpy`` twins, the selection's tie rule, what sees only
blocks (the prefix cache, eviction and requeue) carrying the indexer's keys,
the host's counts of what the attention reads, and a model WITHOUT an indexer
keeping the pool, the kernels and the record it had.

Everything here is float32 on the CPU at tiny widths: ``topk`` 8, contexts
of 3-6 x that, blocks of 4. The tolerance 2e-5 logit-std is ten times what
the rounding of two float32 programs that sum in different orders reads here
(1e-6); a row that attends a wrong set reads 0.05 to several."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import dsa_faults, parity
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession, dsa)
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
from deepspeedsyclsupport_tpu.inference.v2.ragged import (
    SequenceDescriptor, selection_work)
from deepspeedsyclsupport_tpu.models import ModelConfig, build_model
from deepspeedsyclsupport_tpu.models.transformer import SELECTED_ATTN_WRITE
from deepspeedsyclsupport_tpu.ops import sparse_index
from deepspeedsyclsupport_tpu.ops.paged_attention import (
    ragged_prefill_attention_pallas, ragged_prefill_attention_reference)
from tests.family_harness import Harness, engines, family  # noqa: F401

TOL = 2e-5
TOPK, V = 8, 256
HF = {"model_type": "KeyeVL2", "hidden_size": 64, "intermediate_size": 96,
      "moe_intermediate_size": 32, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": V, "num_experts": 4, "num_local_experts": 4,
      "num_experts_per_tok": 3, "norm_topk_prob": True,
      "rms_norm_eps": 1e-6, "rope_theta": 10000000,
      "rope_scaling": {"mrope_section": [2, 3, 3]},
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                    "topk": TOPK},
      "reduced": {"num_experts": {"published": 8, "run": 4}}}
ATTN = {"xla": dict(prefill_attn="xla", decode_attn="xla"),
        "kernels": dict(prefill_attn="kernel_interpret",
                        decode_attn="pallas_interpret", atom_q_size=8)}
ENGINE = {"block_size": 4, "max_context": 64, "max_tokens_per_batch": 16,
          "max_sequences": 4, "num_blocks": 48, **ATTN["xla"]}
PROMPT = np.random.default_rng(0).integers(0, V, 60).tolist()
H = Harness(HF, ENGINE, [PROMPT])


@pytest.fixture(scope="module")
def built():
    """The preset at the tiny widths, half its router's experts held, every
    leaf moved off its init (the norm scales are constants there and the
    indexer's LayerNorm bias zero: a program that left one out would not
    show); the experts and the attention at FULL weight (``routed_write_share``
    None, ``wo`` scaled back up from ``SELECTED_ATTN_WRITE``: the two shares
    are the chip's draw against bf16 router and selection flips)."""
    model = build_model(
        "keye-vl2-30b-a3b", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, vocab_size=V, num_experts=8, num_experts_per_tok=3,
        num_experts_held=4, index_topk=TOPK, index_heads=2, index_head_dim=8,
        max_seq_len=128, routed_write_share=None, dtype="float32")
    def drawn():      # ONE program: a draw a leaf is one a shape otherwise
        params = model.init_params(jax.random.PRNGKey(3))
        attn = params["layers"]["attn"]
        attn["wo"] = attn["wo"] / SELECTED_ATTN_WRITE
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
        moved = [x * (1.0 + 0.2 * jax.random.normal(k, x.shape))
                 if x.ndim > 1
                 else x + 0.2 * jnp.abs(x).mean()
                 * jax.random.normal(k, x.shape) + 0.05 * (x.shape == (8,))
                 for x, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(tree, moved)
    return model, jax.jit(drawn)()


def dense_reference(family, params, ids):
    """Another selection (every key a row sees): the family's blocks, walked
    by hand."""
    from benchmark import reference as ref

    arch = family.arch(HF)
    return np.asarray(ref.decoder_logits(
        params, np.asarray(ids, np.int32),
        lambda p, x: family.block(arch, p, x, select="all")[0],
        lambda p, x: ref.rms_norm(p, x, arch["norm_eps"])))


# ------------------------------------------------------- against the family
@pytest.mark.parametrize("attn", sorted(ATTN))
def test_served_logits_are_the_references(family, built, engines, attn):
    """A 37-token prompt prefilled in chunks of 16 (contexts to 4.6 x
    ``topk``), then ten of its own greedy tokens through the pool: every
    row's logits are the full forward's. ``kernels``: the scores, the
    selection and the masked ragged kernel in interpret mode over atoms of
    8 rows, the one-token rows through the gather."""
    served, tokens = parity.served_logits(
        engines(**ATTN[attn]), 0, PROMPT[:37], 10)
    want = H.reference(built[1], PROMPT[:37] + tokens)[36:]
    assert parity.row_errors(served, want).max() < TOL
    # and the selection MATTERS here: dense attention reads otherwise
    dense = dense_reference(family, built[1], PROMPT[:37] + tokens)[36:]
    assert parity.row_errors(dense, want).max() > 0.02


@pytest.mark.parametrize("fault", dsa_faults.FAULTS)
def test_a_planted_fault_is_refused_by_the_harness_comparison(built, fault):
    """ISSUE 45's four wrong programs (``benchmark.dsa_faults``: what the
    chip is held to at the cell's widths), planted in the served program at
    a context of 4.6 x ``topk``: each comes out beyond ``benchmark.parity``'s
    own limit, by its own comparison, where the sound program reads under
    2e-5 (the test above)."""
    with dsa_faults.planted(fault):
        served, tokens = parity.served_logits(
            H.engine_of(*built), 0, PROMPT[:37], 10)
    want = H.reference(built[1], PROMPT[:37] + tokens)[36:]
    assert parity.row_errors(served, want).max() > parity.TOLERANCE
    # and the plant is lifted again (a new engine: a new trace)
    served, tokens = parity.served_logits(H.engine_of(*built), 0,
                                          PROMPT[:37], 3)
    want = H.reference(built[1], PROMPT[:37] + tokens)[36:]
    assert parity.row_errors(served, want).max() < TOL


def test_within_topk_the_attention_is_dense(family, built, engines):
    """While ``t + 1 <= topk`` a row attends everything it sees: the served
    logits of an 8-token prompt are the dense reference's."""
    served, _ = parity.served_logits(engines(), 0, PROMPT[:TOPK], 0)
    dense = dense_reference(family, built[1], PROMPT[:TOPK])[-1:]
    assert parity.row_errors(served, dense).max() < TOL


def test_mixed_rounds_serve_prompts_beside_decodes(built, engines):
    """Four streams through a session, prompts arriving while others
    decode (mixed ``ragged_forward`` rounds: atoms and one-token rows in one
    batch): each stream's tokens are the reference's greedy choice."""
    eng = engines(**ATTN["kernels"])
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    out = _drive(sess, REQUESTS)
    for uid, prompt, _ in REQUESTS[1:3]:
        rows = H.reference(built[1], prompt + out[uid])
        assert rows[len(prompt) - 1:-1].argmax(-1).tolist() == out[uid]
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round" and r["data"]["program"]]
    assert all("sel_pairs" in d and "dec_sel_tokens" in d for d in rounds)
    # what the one-token rows walk: the whole table's share, never more
    assert all(0 < d["dec_walk_keys"] <= 2 * d["decode_rows"] * 64
               for d in rounds if d["decode_rows"])
    assert any(d["sel_pairs"] and d["dec_sel_tokens"] for d in rounds)
    assert all(d["sel_pairs"] <= d["attn_pairs"]
               and d["dec_sel_tokens"] <= d["dec_ctx_tokens"] for d in rounds)


# ------------------------------------------------------------- the kernels
def _atoms(seed, a=3, r=8, c=48, hi=2, di=8):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((a, r, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((a, r, hi)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, c, di)), jnp.float32)
    return q, w, k


@pytest.mark.parametrize("tile", ["atoms", "one_row_wide_step"])
def test_the_scores_kernel_is_its_twin(tile, monkeypatch):
    """``atoms``: tiles of 8 rows of either sequence. ``one_row_wide_step``:
    every row a tile of its own sequence (the one-token rows' route) over
    600 keys, the step 256 keys by the rule (a budget that gives 8 rows 128)
    where the rule reads the tile's shape: a tile scores what its row may
    see, writes zeros past the step that holds its last key, and a dead
    tile scores nothing."""
    if tile == "atoms":
        q, w, k = _atoms(0)
        tile_seq, tile_hi = jnp.asarray([1, 0, 1]), jnp.asarray([40, 48, 0])
        step = 128
    else:
        monkeypatch.setattr(sparse_index, "SCORE_STEP_BYTES", 300_000)
        assert [sparse_index.score_keys(r, 2, 8, 4, 600)
                for r in (1, 64)] == [256, 128]
        q, w, k = _atoms(3, a=4, r=1, c=600)
        k = jnp.concatenate([k, k[::-1] * 0.5])        # four sequences
        tile_seq, tile_hi = jnp.arange(4), jnp.asarray([600, 130, 0, 300])
        step = 256
    want = np.asarray(sparse_index.index_scores_reference(
        q, w, k, tile_seq, scale=0.25))
    got = np.asarray(sparse_index.index_scores_pallas(
        q, w, k, tile_seq, tile_hi, scale=0.25, interpret=True))
    assert got.shape == want.shape
    for a, hi in enumerate(np.asarray(tile_hi)):
        walked = min(-(-hi // step) * step, want.shape[-1])
        np.testing.assert_allclose(got[a, :, :walked], want[a, :, :walked],
                                   rtol=1e-5, atol=1e-6)
        assert not got[a, :, walked:].any()   # a dead tile: nothing at all


def test_the_scores_step_follows_the_tiles_rows():
    """At the cell's widths (16 heads of 64, bf16 keys, tables of 49,152):
    512 keys a step under an atom of 128 rows, as it was; thousands under
    one row, whole steps of the table."""
    assert sparse_index.score_keys(128, 16, 64, 2, 49152) == 512
    one = sparse_index.score_keys(1, 16, 64, 2, 49152)
    assert one >= 2048 and 49152 % one == 0
    assert sparse_index.score_keys(1, 16, 64, 2, 300) == 384


@pytest.mark.parametrize("ties", [False, True])
def test_the_selection_kernel_is_its_twin_and_breaks_ties_low(ties):
    """Rows at positions 29-36, 5-12 (five live) and a dead tile, ``k`` 8;
    with ``ties`` the scores take four values only, so the k-th value is
    tied in every row and the LOWER positions win (``lax.top_k``'s rule)."""
    rng = np.random.default_rng(1)
    scores = rng.standard_normal((3, 8, 48)).astype(np.float32)
    if ties:
        scores = np.round(scores).clip(-1, 2) + 0.0
    pos0, qlen = jnp.asarray([29, 5, 0]), jnp.asarray([8, 5, 0])
    want = np.asarray(sparse_index.select_topk_reference(
        jnp.asarray(scores), pos0, qlen, k=8))
    got = np.asarray(sparse_index.select_topk_pallas(
        jnp.asarray(scores), pos0, qlen, k=8, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert (want[0].sum(-1) == 8).all()
    assert want[1].sum(-1).tolist() == [6, 7, 8, 8, 8, 0, 0, 0]
    assert not want[2].any()
    if ties:        # by hand: the best values first, a tied value low first
        for r in range(8):
            seen = scores[0, r, :30 + r]
            order = sorted(range(len(seen)), key=lambda s: (-seen[s], s))
            assert sorted(order[:8]) == np.flatnonzero(want[0, r]).tolist()


@pytest.mark.parametrize("ties", [False, True])
def test_the_selection_kernel_takes_rows_at_positions_of_their_own(
        ties, monkeypatch):
    """ONE tile of six rows, each at its own sequence's last position (the
    one-token rows of a forward): contexts 200, 0 (a dead row), 5 (fewer
    than ``k``: keeps all), 40, 129 and 8 of 300 keys, walked in chunks of
    128 up to the longest's (the third chunk is blanked, not walked). With
    ``ties`` every row's k-th value is tied and the LOWER positions win."""
    monkeypatch.setattr(sparse_index, "SELECT_CHUNK", 128)
    rng = np.random.default_rng(5)
    scores = rng.standard_normal((1, 6, 300)).astype(np.float32)
    if ties:
        scores = np.round(scores).clip(-1, 2) + 0.0
    lens = np.asarray([200, 0, 5, 40, 129, 8])
    pos0, qlen = jnp.asarray(lens - 1)[None], jnp.asarray([6])
    want = np.asarray(sparse_index.select_topk_reference(
        jnp.asarray(scores), pos0, qlen, k=8))
    got = np.asarray(sparse_index.select_topk_pallas(
        jnp.asarray(scores), pos0, qlen, k=8, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert want[0].sum(-1).tolist() == [8, 0, 5, 8, 8, 8]
    for r, n in enumerate(lens):     # by hand: best first, a tied value low
        seen = scores[0, r, :n]
        order = sorted(range(n), key=lambda s: (-seen[s], s))
        assert sorted(order[:8]) == np.flatnonzero(want[0, r]).tolist()
    # a tile of consecutive rows says the same through either form of pos0
    atom = np.asarray(sparse_index.select_topk_pallas(
        jnp.asarray(scores), jnp.asarray([[100, 101, 102, 103, -1, -1]]),
        jnp.asarray([6]), k=8, interpret=True))
    np.testing.assert_array_equal(atom, np.asarray(
        sparse_index.select_topk_pallas(
            jnp.asarray(scores), jnp.asarray([100]), jnp.asarray([4]), k=8,
            interpret=True)))


@pytest.mark.parametrize("rows", ["full", "short", "empty", "over"])
def test_the_positions_are_the_masks_set(rows):
    """``positions_from_mask``: exactly the mask's positions, rising, then
    the row's width in the slots left over; of more than ``k`` the lowest."""
    rng = np.random.default_rng(6)
    c, k = 700, 16
    mask = np.zeros((5, c), np.int8)
    for r in range(5):
        n = {"full": k, "short": 3 * r, "empty": 0, "over": k + 9}[rows]
        mask[r, rng.permutation(c)[:n]] = 1
    if rows == "short":
        mask[4] = 0
        mask[4, [127, 128, 255, 256, 699]] = 1     # the groups' edges
    got = np.asarray(sparse_index.positions_from_mask(jnp.asarray(mask),
                                                      k=k))
    for r in range(5):
        held = np.flatnonzero(mask[r])[:k]
        assert got[r].tolist() == held.tolist() + [c] * (k - len(held))


@pytest.mark.parametrize("layer", [0, 1])
def test_the_rows_route_through_the_kernels_is_the_twins(built, engines,
                                                         layer):
    """``dsa.attend_rows`` over a served engine's own pools (three sequences
    of 41, 5 and 23 cached tokens and a slot with no row; ``topk`` 8):
    scores and selection through the two kernels in interpret mode give the
    rows the ``jax.numpy`` twins give."""
    model, _ = built
    eng = engines()
    for uid, n in enumerate((41, 5, 23)):
        eng.put([uid], [PROMPT[uid:uid + n]])
    descs = [eng.seqs[u] for u in range(3)]
    positions, tables, active, _ = eng._slot_arrays(descs)
    assert positions[:3].tolist() == [41, 5, 23] and not active[3]
    lens = jnp.asarray(np.where(active, positions, 0), jnp.int32)
    cfg = model.config
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (4, cfg.num_heads, cfg.head_dim))
    q_i = jax.random.normal(ks[1], (4, cfg.index_heads, cfg.index_head_dim))
    w = jax.random.normal(ks[2], (4, cfg.index_heads))
    k_cache, v_cache, idx = eng.kv.pools
    k_seq = dsa.seq_index_keys(idx, layer, jnp.asarray(tables), 4)
    out = {impl: np.asarray(dsa.attend_rows(
        q, q_i, w, k_seq, k_cache, v_cache, layer, jnp.asarray(tables), lens,
        4, cfg, impl)) for impl in ("xla", "pallas_interpret")}
    np.testing.assert_allclose(out["pallas_interpret"], out["xla"],
                               rtol=1e-5, atol=1e-6)
    assert np.abs(out["xla"][:3]).max() > 0.1 and not out["xla"][3].any()
    eng.flush([0, 1, 2])


def test_the_ragged_kernel_under_a_selection_is_its_twin():
    """Atoms of 8 rows over a pool of 16 blocks of 4: under ``sel`` a pair
    counts only where the mask is nonzero; without it the call is the one
    it was (no operand, the old name)."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((3, 8, 4, 16)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((2, 2, 64, 2, 16)), jnp.float32)
    tables = jnp.asarray(rng.permutation(16)[:12].reshape(1, 12)
                         .repeat(3, 0), jnp.int32)      # one sequence's
    pos0, qlen = jnp.asarray([30, 12, 0]), jnp.asarray([8, 5, 0])
    sel = sparse_index.select_topk_reference(
        jnp.asarray(rng.standard_normal((3, 8, 48)), jnp.float32),
        pos0, qlen, k=8)
    kw = dict(block_size=4, layer=1)
    want = ragged_prefill_attention_reference(
        q, pool[0], pool[1], tables, pos0, qlen, sel=sel, **kw)
    got = ragged_prefill_attention_pallas(
        q, pool[0], pool[1], tables, pos0, qlen, sel=sel, interpret=True,
        **kw)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    dense = ragged_prefill_attention_reference(
        q, pool[0], pool[1], tables, pos0, qlen, **kw)
    assert np.abs(np.asarray(dense - want))[0].max() > 1e-2


# ------------------------------------------------ what sees only blocks
def _drive(sess, requests, rounds=600):
    for uid, prompt, budget in requests:
        assert sess.submit(uid, prompt, budget) == "admitted"
    out = {uid: [] for uid, *_ in requests}
    for _ in range(rounds):
        if sess.idle:
            break
        for ev in sess.step():
            if ev.kind == "token":
                out[ev.uid] += ev.tokens
    assert sess.idle
    return out


REQUESTS = [(u, PROMPT[u:u + 22 + 5 * u], 12) for u in range(4)]


def test_eviction_with_requeue_gives_the_tokens_of_a_roomy_pool(built,
                                                                engines):
    """A block holds its tokens' indexer keys beside their K and V, so a
    stream that is evicted and prefilled again selects as it did: under a
    pool of 16 blocks streams are requeued and finish with the tokens a
    roomy pool gives."""
    roomy = _drive(ServingSession(
        engines(), ServingPolicyConfig(admission="none")), REQUESTS)
    eng = H.engine_of(*built, num_blocks=16)
    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", preempt_policy="requeue"))
    tight = _drive(sess, REQUESTS)
    assert sess.stats()["evicted"] > 0
    assert tight == roomy
    assert eng.allocator.free_blocks == 16


def test_a_prefix_cache_hit_keeps_the_indexer_keys(built, engines):
    """Two prompts that share 24 tokens (six blocks, three times ``topk``):
    the second maps the first's blocks, indexer keys and all, and its
    logits are those of an engine without the cache and the reference's."""
    shared, tail = PROMPT[:24], PROMPT[24:33]
    cold, _ = parity.served_logits(engines(), 2, shared + tail, 3)
    eng = H.engine_of(*built)
    eng.install_prefix_cache()
    parity.served_logits(eng, 1, shared + PROMPT[40:45], 2)
    eng.map_cached_prefix(2, shared + tail)
    assert eng.seqs[2].cached_prefix_len == 24
    rows = [np.asarray(eng.put([2], [tail])[2])]
    tokens = []
    for _ in range(3):
        tokens.append(int(rows[-1].argmax()))
        rows.append(np.asarray(eng.put([2], [[tokens[-1]]])[2]))
    np.testing.assert_allclose(np.stack(rows), cold, atol=1e-5)
    want = H.reference(built[1], shared + tail + tokens)[-4:]
    assert parity.row_errors(np.stack(rows), want).max() < TOL


# ------------------------------------------------------ shapes and counts
def test_the_pool_has_a_third_array_on_the_same_slots(built):
    eng = H.engine_of(*built)       # (a pool of zeros: nothing has run)
    kv = eng.kv
    assert kv.idx.shape == (2, 48 * 4 // 2, 2 * 8) and len(kv.pools) == 3
    per_token = 2 * (2 * 2 * 16 + 8) * 4         # layers x (K, V + idx) x f32
    assert kv_pool_stats(kv, eng.allocator)["pool_bytes"] == per_token * 192
    assert kv.with_pools([p + 1 for p in kv.pools]).idx.min() == 1


@pytest.mark.parametrize("cached,new,want", [
    (0, 5, (15, 0, 0)),            # rows at 0-4 see 1..5 each
    (0, 12, (36 + 4 * 8, 0, 0)),   # rows 0-7 see 1..8, four more see topk
    (20, 6, (48, 0, 0)),           # every row past topk
    (6, 4, (7 + 8 + 8 + 8, 0, 0)),
    # a one-token row: what is kept of its context, and what the scores
    # (steps of 16) and the selection (chunks of 4) walk for it
    (5, 1, (0, 6, 16 + 8)), (30, 1, (0, 8, 32 + 32))])
def test_the_hosts_count_of_what_is_selected(cached, new, want):
    d = SequenceDescriptor(uid=0)
    d.n_cached = cached
    assert selection_work([d], [new], TOPK, (16, 4)) == want


def test_the_rows_walk_follows_the_longest_row_and_the_route(built):
    """Three one-token rows at contexts 40, 9 and 17 beside a chunk: each
    row's context to its scores step, every row the LONGEST's to the
    selection's chunk; the twins' route walks the whole table a row, twice.
    An engine says which route each program takes (``dsa_rows`` decisions)
    and counts by it."""
    from deepspeedsyclsupport_tpu.monitor import telemetry as tel

    descs = [SequenceDescriptor(uid=u) for u in range(4)]
    for d, n in zip(descs, (39, 8, 16, 50)):
        d.n_cached = n
    lengths = [1, 1, 1, 6]
    assert selection_work(descs, lengths, TOPK, (16, 32))[2] == \
        (48 + 16 + 32) + 3 * 64
    assert selection_work(descs, lengths, TOPK, (64, 64))[2] == 2 * 3 * 64
    tel.setup_ledger_store.reset()
    eng = H.engine_of(*built, **ATTN["kernels"])
    assert eng._dsa_walk == {"ragged_forward": (64, 64),
                             "decode_forward": (64, 64)}
    routes = [r for r in tel.setup_ledger() if r["kind"] == "decision"
              and r["name"] == "dsa_rows"]
    assert [(r["program"], r["impl"], r["score_keys"]) for r in routes] == [
        ("ragged_forward", "pallas_interpret", 64),
        ("decode_forward", "pallas_interpret", 64)]
    mixed = H.engine_of(*built, decode_attn="pallas_interpret")
    assert mixed._dsa_walk["ragged_forward"] == (64, 64)   # the whole table


def test_a_model_without_an_indexer_keeps_what_it_had():
    """No third array, no selected counts on the record, and the ragged
    kernel's call takes the operands it took: the programs of every other
    model are the ones they were."""
    model = build_model("tiny", dtype="float32")
    eng = InferenceEngineV2(model, model.init_params(), dtype=jnp.float32,
                            **{**ENGINE, **ATTN["xla"]})
    assert eng.kv.idx is None and len(eng.kv.pools) == 2
    assert "idx" not in {
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(eng.kv)[0]}
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    _drive(sess, [(0, PROMPT[:20], 3)])
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    assert rounds and not any("sel_pairs" in d for d in rounds)
    q = jnp.zeros((2, 8, 4, 16), jnp.float32)
    pool = jnp.zeros((64, 2, 16), jnp.float32)
    args = (q, pool, pool, jnp.zeros((2, 12), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
    call = lambda **kw: jax.make_jaxpr(  # noqa: E731
        lambda *a: ragged_prefill_attention_pallas(
            *a, block_size=4, interpret=True, **kw))(*args, **{})
    plain = str(call())
    assert "ragged_prefill" in plain and "dsa_prefill" not in plain
    masked = jax.make_jaxpr(lambda *a, sel: ragged_prefill_attention_pallas(
        *a, block_size=4, interpret=True, sel=sel))(
            *args, sel=jnp.zeros((2, 8, 48), jnp.int8))
    assert "dsa_prefill" in str(masked)


@pytest.mark.parametrize("what,kw", [
    ("index_heads", dict(index_topk=8)),
    # (latent attention it is written for since PR 65:
    # tests/unit/test_glm5_config.py; a looped stack it is not)
    ("looped stack", dict(index_topk=8, index_heads=2, index_head_dim=8,
                          total_ut_steps=2)),
    ("window", dict(index_topk=8, index_heads=2, index_head_dim=8,
                    sliding_window=16))])
def test_an_indexer_refuses_what_it_is_not_written_for(what, kw):
    with pytest.raises(ValueError, match="index_topk"):
        ModelConfig(**kw)


def test_training_refuses_the_indexer_by_name(built):
    model, params = built
    with pytest.raises(NotImplementedError, match="index_topk"):
        model.loss(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                   jax.random.PRNGKey(0))
