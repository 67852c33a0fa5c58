"""Latent attention (MLA), hyper-connection streams and the sigmoid router
with a shared expert, beside the modules they touch: both paged kernels on a
latent pool against their references, the pool with no V leaf (its stats, its
copy-on-write, a prefix-cache hit), the router's contract, the Sinkhorn
matrix at both clamps, YaRN's blend, and what the round's record counts of
the attention it launched."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (
    build_block_copy_fn, kv_pool_stats)
from deepspeedsyclsupport_tpu.inference.v2.ragged import (
    SequenceDescriptor, attention_work)
from deepspeedsyclsupport_tpu.models import build_model, get_config
from deepspeedsyclsupport_tpu.models.layers import rope_frequencies
from deepspeedsyclsupport_tpu.ops import paged_attention as pa
from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop
from tests.family_harness import Harness
from tests.unit import stream_ends

TINY = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_layers=3, first_k_dense_replace=1, num_heads=4, num_kv_heads=4,
    head_dim=24, vocab_size=512, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
    num_experts_per_tok=3, max_seq_len=256, dtype="float32",
    rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16})
ENGINE = dict(max_context=128, max_sequences=4, num_blocks=32, block_size=16,
              max_tokens_per_batch=16, prefill_attn="xla", decode_attn="xla")


@pytest.fixture(scope="module")
def tiny():
    model = build_model("xing4-29b-a4b", **TINY)
    # (ONE program: a draw a leaf is one a shape otherwise)
    return model, jax.jit(model.init_params)(jax.random.PRNGKey(3))


SERVING = Harness(None, ENGINE)


# ------------------------------------------------------------ the kernels
# the cell's widths: rows of 576 lane-padded to 640, the value their leading
# 512, ONE kv head under 32 query heads
H, DK, DPAD, DV, BS, BPS = 32, 576, 640, 512, 16, 4


def latent_case(seed, n):
    rng = np.random.default_rng(seed)
    pool = np.zeros((2, 12 * BS, DPAD), np.float32)
    pool[..., :DK] = rng.standard_normal((2, 12 * BS, DK))
    q = np.zeros((n, H, DPAD), np.float32)
    q[..., :DK] = rng.standard_normal((n, H, DK)) * 0.3
    return jnp.asarray(q), jnp.asarray(pool), rng


def test_ragged_kernel_reads_a_latent_pool_with_a_dead_atom_and_a_pad_row():
    """Three atoms of 8 rows: one whole, one of 5 live rows (3 pad), one
    dead. V is the leading 512 lanes of the K tile; the output is 512 wide."""
    bq = 8
    q, pool, rng = latent_case(0, 3 * bq)
    q = q.reshape(3, bq, H, DPAD)
    tables = jnp.asarray(rng.permutation(12)[:3 * BPS].reshape(3, BPS),
                         jnp.int32)
    pos0 = jnp.asarray([20, 3, 0], jnp.int32)
    qlen = jnp.asarray([bq, 5, 0], jnp.int32)
    kw = dict(block_size=BS, layer=jnp.int32(1), v_dim=DV)
    got = pa.ragged_prefill_attention_pallas(q, pool, None, tables, pos0,
                                             qlen, interpret=True, **kw)
    want = pa.ragged_prefill_attention_reference(q, pool, None, tables, pos0,
                                                 qlen, **kw)
    assert got.shape == (3, bq, H, DV)
    live = np.arange(bq)[None, :] < np.asarray(qlen)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    assert not np.asarray(got)[2].any()          # the dead atom wrote zeros
    # and V really is the K row's head: a hand-made softmax over one row
    s = (np.asarray(q)[0, 0, 0] @ np.asarray(pool)[1][
        np.asarray(tables)[0].repeat(BS) * BS + np.tile(np.arange(BS), BPS)
    ][:21].T) / np.sqrt(DPAD)
    w = np.exp(s - s.max())
    rows = np.asarray(pool)[1][np.asarray(tables)[0].repeat(BS) * BS
                               + np.tile(np.arange(BS), BPS)][:21]
    np.testing.assert_allclose(np.asarray(got)[0, 0, 0],
                               (w / w.sum()) @ rows[:, :DV], atol=2e-5)


def test_decode_kernel_reads_a_latent_pool_with_an_idle_slot():
    q, pool, rng = latent_case(1, 3)
    tables = jnp.asarray(rng.permutation(12)[:3 * BPS].reshape(3, BPS),
                         jnp.int32)
    lens = jnp.asarray([37, 0, 1], jnp.int32)         # slot 1 is idle
    kw = dict(block_size=BS, layer=jnp.int32(0), v_dim=DV)
    got = pa.paged_decode_attention_pallas(q, pool, None, tables, lens,
                                           interpret=True, **kw)
    want = pa.paged_decode_attention_reference(q, pool, None, tables, lens,
                                               **kw)
    assert got.shape == (3, H, DV)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=2e-5)
    assert not np.asarray(got)[1].any()


# a loop step of P blocks (block_size 16, 12 blocks a table row): what each
# case puts at the step's edges, as (first position, live rows) of the
# ragged entry's 8-row tiles and as the one-row entry's context lengths
WIDE_STEP = {
    # neither a multiple of P x 16 keys, nor of 16
    "a_ragged_tail": dict(atoms=[(70, 8), (101, 5)], lens=[78, 105, 33]),
    # all of it inside the first block of the first step
    "shorter_than_a_step": dict(atoms=[(0, 8), (3, 6)], lens=[1, 9, 14]),
    # a tile with no row between two that have some
    "a_dead_tile": dict(atoms=[(40, 8), (0, 0), (90, 3)], lens=[64, 0, 100]),
    # the first block inside the window is block 3, 5 or 2: no multiple of 4
    "a_window_off_the_steps": dict(atoms=[(90, 8), (121, 8)],
                                   lens=[100, 130, 75], window=40),
    # a context longer than its table of 11 blocks (176 keys; no multiple of
    # a step of 2 or 4): what the table holds, and no key of the step's rest
    "past_the_tables_end": dict(atoms=[(170, 8), (100, 8)],
                                lens=[180, 176, 90], bps=11),
}


@pytest.mark.parametrize("entry", ["ragged_prefill", "paged_decode"])
@pytest.mark.parametrize("pages", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(WIDE_STEP))
def test_a_step_of_several_blocks_reads_what_one_block_a_step_does(
        monkeypatch, case, pages, entry):
    """Both entries of the kernel on a latent pool whose blocks OUTSIDE the
    tables' live part are NaN (another sequence's, or never written): a
    step's blocks past the context's end, or below the window's start, must
    not be read as they lie, since 0 x NaN is NaN in ``p v``."""
    monkeypatch.setattr(pa, "_kv_pages_per_step",
                        lambda *a: pages if a[-1] else 1)
    spec = WIDE_STEP[case]
    window = spec.get("window")
    bq, bps, blocks = 8, spec.get("bps", 12), 40
    rng = np.random.default_rng(sorted(WIDE_STEP).index(case))
    if entry == "ragged_prefill":
        pos0, qlen = (jnp.asarray(x, jnp.int32) for x in zip(*spec["atoms"]))
        hi = np.asarray(pos0 + qlen)
    else:
        hi = np.asarray(spec["lens"])
        pos0, qlen = jnp.maximum(hi - 1, 0), hi > 0
    n = len(hi)
    tables = rng.permutation(blocks)[:n * bps].reshape(n, bps)
    # live: the blocks the loop of one block a step reads (from the block
    # that holds row 0's window start to the one that holds the last key)
    lo = np.zeros(n, int) if window is None else \
        np.maximum(np.asarray(pos0) + 1 - window, 0) // BS
    live = [tables[i, lo[i]:-(-hi[i] // BS)] for i in range(n)]   # <= bps
    pool = np.full((2, blocks * BS, DPAD), np.nan, np.float32)
    for blk in np.concatenate(live):
        pool[:, blk * BS:(blk + 1) * BS] = 0
        pool[:, blk * BS:(blk + 1) * BS, :DK] = rng.standard_normal(
            (2, BS, DK))
    q = np.zeros((n, bq, H, DPAD), np.float32)
    q[..., :DK] = rng.standard_normal((n, bq, H, DK)) * 0.3
    kw = dict(block_size=BS, layer=jnp.int32(1), v_dim=DV, window=window)
    # the reference gathers every block of the table: give it zeros where
    # the kernel must not look
    clean = jnp.asarray(np.nan_to_num(pool))
    if entry == "ragged_prefill":
        args = (jnp.asarray(tables, jnp.int32), pos0, qlen)
        got = pa.ragged_prefill_attention_pallas(
            jnp.asarray(q), jnp.asarray(pool), None, *args, interpret=True,
            **kw)
        want = pa.ragged_prefill_attention_reference(
            jnp.asarray(q), clean, None, *args, **kw)
        rows = np.arange(bq)[None, :] < np.asarray(qlen)[:, None]
    else:
        args = (jnp.asarray(tables, jnp.int32), jnp.asarray(hi, jnp.int32))
        got = pa.paged_decode_attention_pallas(
            jnp.asarray(q[:, 0]), jnp.asarray(pool), None, *args,
            interpret=True, **kw)
        want = pa.paged_decode_attention_reference(
            jnp.asarray(q[:, 0]), clean, None, *args, **kw)
        rows = hi > 0
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[rows], np.asarray(want)[rows], atol=2e-5)
    assert not got[~rows].any()              # dead rows and tiles: zeros


def test_a_pool_without_v_needs_v_dim():
    q, pool, _rng = latent_case(2, 2)
    with pytest.raises(ValueError, match="v_dim"):
        pa.paged_decode_attention_pallas(
            q, pool, None, jnp.zeros((2, BPS), jnp.int32),
            jnp.ones((2,), jnp.int32), block_size=BS, interpret=True)


def test_heads_are_tiled_by_the_shape_under_one_kv_head(monkeypatch):
    """128 rows x 32 heads x 640 under ONE kv head models at 80 MiB: two
    tiles of 16 heads. phi-2's and OLMoE's 32 x 128 stay one tile, as does
    any model with more than one kv head, and a one-row decode tile."""
    assert pa._head_tile(128, 32, 1, 640, 64, 2) == 16
    assert pa._head_tile(128, 32, 32, 128, 64, 2) == 32     # phi-2, OLMoE
    assert pa._head_tile(128, 32, 8, 128, 64, 2) == 32      # mistral
    assert pa._head_tile(1, 32, 1, 640, 64, 2) == 32        # decode
    assert pa._ragged_vmem_limit(128, 16, 1, 640, 64, 2) <= pa._VMEM_CAP
    # the tiled grid computes what the untiled one does (budget forced low)
    bq = 8
    q, pool, rng = latent_case(3, 2 * bq)
    q = q.reshape(2, bq, H, DPAD)
    tables = jnp.asarray(rng.permutation(12)[:2 * BPS].reshape(2, BPS),
                         jnp.int32)
    args = (q, pool, None, tables, jnp.asarray([9, 30], jnp.int32),
            jnp.asarray([bq, 6], jnp.int32))
    kw = dict(block_size=BS, layer=jnp.int32(1), v_dim=DV, interpret=True,
              alibi=np.linspace(0.01, 0.3, H).astype(np.float32))
    whole = pa.ragged_prefill_attention_pallas(*args, **kw)
    monkeypatch.setattr(pa, "_HEAD_TILE_BUDGET", 1 << 20)
    assert pa._head_tile(bq, H, 1, DPAD, BS, 4) == 16
    tiled = pa.ragged_prefill_attention_pallas(*args, **kw)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(whole),
                               atol=1e-6)


def test_the_step_is_chosen_after_the_tile_and_leaves_it_alone(monkeypatch):
    """The head tile and the atom's rows are decided at one KV block a step,
    whatever a step then takes: 16 heads x 128 rows (Xing4), 128 heads x 16
    rows (DeepSeek-V2), as accepted; then the blocks a step, from the tile:
    four under a 2,048-row tile (2 MiB of scores), eight under one row."""
    xing4, dsv2 = (32, 1, 640, 64, 2), (128, 1, 640, 64, 2)

    def chosen():
        return [(pa.default_atom_rows(128, *shape),
                 pa._head_tile(pa.default_atom_rows(128, *shape), *shape))
                for shape in (xing4, dsv2)]

    assert chosen() == [(128, 16), (16, 128)]
    assert pa._kv_pages_per_step(128, 16, *xing4[1:], True) == 4
    assert pa._kv_pages_per_step(16, 128, *dsv2[1:], True) == 4
    assert pa._kv_pages_per_step(1, 32, *xing4[1:], True) == 8
    assert pa._kv_pages_per_step(1, 128, *dsv2[1:], True) == 8
    assert pa.kv_step_keys(128, *xing4, True) == 4 * 64
    assert pa.kv_step_keys(1, *dsv2, True) == 8 * 64
    # a step may take what room is left, never the tile's
    assert pa._ragged_vmem_need(128, 16, 1, 640, 64, 2, 4) > \
        pa._HEAD_TILE_BUDGET > pa._ragged_vmem_need(128, 16, 1, 640, 64, 2)
    assert pa._ragged_vmem_limit(128, 16, 1, 640, 64, 2, 4) == pa._VMEM_CAP
    # under a tighter cap the step shrinks and the tile stays
    monkeypatch.setattr(pa, "_VMEM_CAP", 48 << 20)
    assert pa._kv_pages_per_step(128, 16, *xing4[1:], True) == 2
    assert chosen() == [(128, 16), (16, 128)]
    # and a rule that asked for more would move neither
    monkeypatch.setattr(pa, "_kv_pages_per_step", lambda *a: 8)
    assert chosen() == [(128, 16), (16, 128)]


# (rows, heads, kv heads, d, block, itemsize) of a K-and-V pool's tile ->
# blocks a step: what bounds it
KV_TILE_PAGES = {
    # keye-video-sat: one kv head's [1024, 512] float32 scores are the 2 MiB
    "keye_atoms": ((128, 32, 4, 128, 64, 2), 8),
    # 8 kv heads x 128 move 256 KiB a block of K and V: eight are the 2 MiB
    "gqa8_atoms": ((128, 32, 8, 128, 64, 2), 8),
    "olmoe_atoms": ((128, 16, 16, 128, 64, 2), 4),      # 512 KiB a block
    # phi-2 (d 80 stored as 128): 1 MiB a block, and its contexts are short
    "phi2_atoms": ((128, 32, 32, 128, 64, 2), 2),
    "phi2_atoms_f32": ((128, 32, 32, 128, 64, 4), 1),
    # one kv head under 32 heads: 4,096 rows of scores
    "mqa_atoms": ((128, 32, 1, 128, 64, 2), 2),
    # the one-row tile keeps the loop over blocks it always was
    "phi2_row": ((1, 32, 32, 128, 64, 2), 1),
    "gqa8_row": ((1, 32, 8, 128, 64, 2), 1),
    "mqa_row": ((1, 16, 1, 128, 64, 2), 1),
}


@pytest.mark.parametrize("name", sorted(KV_TILE_PAGES))
def test_a_k_and_v_pools_step_follows_its_tile(name):
    """ONE rule for every pool: the most blocks at which one kv head's
    scores, the step's K and V and the model of VMEM stay under their
    budgets; only a K-and-V pool's one-row tile is held at one."""
    tile, pages = KV_TILE_PAGES[name]
    assert pa._kv_pages_per_step(*tile, False) == pages
    rows, heads, kvh, d, bs, itemsize = tile
    assert pa.kv_step_keys(*tile, False) == pages * bs
    # under a selection a step is whole lane tiles of 128 keys, no fewer
    assert pa.kv_step_keys(*tile, False, True) == max(pages, 2) * bs
    assert pa._selection_pages(pages, 128) == pages
    assert pa._selection_pages(1, 4) == pa._MAX_STEP_PAGES
    if pages > 1:
        assert rows * heads // kvh * pages * bs * 4 <= pa._STEP_SCORE_BYTES
        assert 2 * pages * bs * kvh * d * itemsize <= pa._STEP_KV_BYTES
        assert pa._ragged_vmem_limit(*tile, pages, True) <= pa._VMEM_CAP
    if pages < pa._MAX_STEP_PAGES and rows > 1:     # and twice would not
        assert (rows * heads // kvh * 2 * pages * bs * 4
                > pa._STEP_SCORE_BYTES
                or 4 * pages * bs * kvh * d * itemsize > pa._STEP_KV_BYTES)


# --------------------------------------------------------------- the pool
def test_the_latent_pool_has_one_leaf_of_padded_rows(tiny):
    """``head_dim_lane_pad=128`` is what the TPU gets by default: 40-wide
    rows pad to 128 here as 576 pads to 640 there; no V, and the counters'
    ``load`` has a row for each EXPERT layer only."""
    eng = SERVING.engine_of(*tiny, dtype="bfloat16", head_dim_lane_pad=128)
    slots = ENGINE["num_blocks"] * ENGINE["block_size"]
    assert eng.kv.v is None and eng.kv.k.shape == (3, slots, 128)
    # k, load, touched, tiles
    assert len(jax.tree_util.tree_leaves(eng.kv)) == 4
    assert eng.kv.moe.load.shape == (2, 8)
    stats = kv_pool_stats(eng.kv, eng.allocator)
    assert stats["pool_bytes"] == slots * 3 * 128 * 2
    assert stats["blocks_free"] == ENGINE["num_blocks"]


def test_real_widths_give_7680_bytes_a_token():
    cfg = get_config("xing4-29b-a4b", num_layers=6)
    assert cfg.latent_kv_dim == 576 and cfg.num_moe_layers == 4
    assert -(-cfg.latent_kv_dim // 128) * 128 * 2 * cfg.num_layers == 7680
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)
    # 29.5 B parameters as published (29B-A4B)
    assert get_config("xing4-29b-a4b").param_count() == pytest.approx(
        29.5e9, rel=5e-3)


def test_block_copy_copies_a_latent_block(tiny):
    eng = SERVING.engine_of(*tiny)
    kv = eng.kv._replace(k=jax.random.normal(jax.random.PRNGKey(0),
                                             eng.kv.k.shape))
    before = np.asarray(kv.k)
    out = build_block_copy_fn(16)(kv, jnp.int32(5), jnp.int32(9))
    after = np.asarray(out.k)
    assert out.v is None
    np.testing.assert_array_equal(after[:, 9 * 16:10 * 16],
                                  before[:, 5 * 16:6 * 16])
    mask = np.ones(before.shape[1], bool)
    mask[9 * 16:10 * 16] = False
    np.testing.assert_array_equal(after[:, mask], before[:, mask])


def test_a_prefix_cache_hit_on_a_latent_pool_changes_no_logit(tiny):
    """The second request shares the first one's two full blocks: its
    logits are what a cold prefill of the same prompt gives."""
    prompt = list(range(5, 45))                       # 40 tokens: 2 blocks
    cold = SERVING.engine_of(*tiny)
    want = np.asarray(cold.put([1], [prompt])[1])
    eng = SERVING.engine_of(*tiny)
    eng.install_prefix_cache()
    eng.put([1], [prompt])
    eng.flush([1])
    got = np.asarray(eng.put([2], [prompt])[2])
    assert eng.seqs[2].cached_prefix_len == 32
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_expert_counters_count_expert_layers_only(tiny):
    eng = SERVING.engine_of(*tiny)
    eng.put([1, 2], [list(range(1, 8)), list(range(20, 61))])
    eng.put([1], [[3]])
    stats = eng.moe_stats()
    assert stats["load"].shape == (2, 8)
    assert stats["live_tokens"] == 7 + 41 + 1
    assert (stats["load"].sum(1) == 3 * stats["live_tokens"]).all()


# ------------------------------------------------------------- the router
def router_case(tiny, bias):
    model, params = tiny
    moe = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["moe"])
    moe = {**moe, "router_bias": jnp.asarray(bias, jnp.float32)}
    x = jax.random.normal(jax.random.PRNGKey(7), (6, 64)) * 2.0
    return model.config, moe, x


def routed(cfg, moe, x):
    """(scores [T, E], the chosen experts' weights [T, k], the chosen
    experts [T, k]) of the layer's own router functions."""
    from deepspeedsyclsupport_tpu.parallel.moe import (router_scores,
                                                       topk_weights)

    scores = router_scores(x @ moe["router"], cfg)
    w, idx = topk_weights(scores, cfg.num_experts_per_tok,
                          cfg.norm_topk_prob, moe["router_bias"],
                          cfg.routed_scaling_factor)
    return np.asarray(scores), np.asarray(w), np.asarray(idx)


def test_the_selection_bias_chooses_and_never_weighs(tiny):
    cfg, moe, x = router_case(tiny, np.zeros(8))
    s, w0, i0 = routed(cfg, moe, x)
    bias = np.zeros(8, np.float32)
    bias[np.argsort(s[0])[0]] = 5.0               # token 0's LEAST liked
    _s, w1, i1 = routed(cfg, {**moe, "router_bias": jnp.asarray(bias)}, x)
    assert set(i1[0]) != set(i0[0]) and np.argsort(s[0])[0] in i1[0]
    for t in range(len(x)):
        # weights are the chosen experts' UNBIASED scores over their sum,
        # times routed_scaling_factor: a function of s alone
        want = s[t, i1[t]] / s[t, i1[t]].sum() * cfg.routed_scaling_factor
        np.testing.assert_allclose(w1[t], want, rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-6)


def test_a_pad_row_reaches_no_expert_and_the_shared_expert_is_beside(tiny):
    cfg, moe, x = router_case(tiny, np.zeros(8))
    live = jnp.asarray([True, True, False, True, False, True])
    out, rows = moe_mlp_nodrop(moe, x, cfg, live)
    assert int(rows.sum()) == cfg.num_experts_per_tok * 4
    # a pad row gets the shared expert's output alone: no routed expert
    shared = moe["shared"]
    g = x @ shared["w_gate"]
    want = (jax.nn.silu(g) * (x @ shared["w_up"])) @ shared["w_down"]
    np.testing.assert_allclose(np.asarray(out)[[2, 4]],
                               np.asarray(want)[[2, 4]], atol=1e-6)
    no_shared, _ = moe_mlp_nodrop(
        {k: v for k, v in moe.items() if k != "shared"}, x, cfg, live)
    np.testing.assert_allclose(np.asarray(out - no_shared), np.asarray(want),
                               atol=1e-5)


# ------------------------------------------------------------ the streams
@pytest.mark.parametrize("clamp", ["min", "max", "both"])
def test_the_sinkhorn_matrix_is_doubly_stochastic_at_the_clamps(tiny, clamp):
    """Inputs that drive the res map to ``mhc_h_res_clamp_min`` / ``_max``
    (exp(-30) .. exp(30), 26 orders apart): after 20 rounds every row and
    every column still sums to 1 within 1e-5."""
    model, params = tiny
    cfg = model.config
    hc = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["hc_attn"])
    n = cfg.hc_mult
    b_res = {"min": -np.ones((n, n)) * 100.0, "max": np.ones((n, n)) * 100.0,
             "both": np.where(np.eye(n) > 0, 100.0, -100.0)}[clamp]
    if clamp != "both":        # not all equal: the matrix must be worked for
        b_res = b_res + np.arange(n * n).reshape(n, n) * 1e-3
    hc = {**hc, "b": hc["b"].at[2 * n:].set(
        jnp.asarray(b_res.reshape(-1), jnp.float32))}
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 5, cfg.hidden_size))
    pre, post, res = M._hc_maps(hc, x, cfg)
    res = np.asarray(res)                             # [row, column, token]
    assert res.shape == (n, n, 5) and (res >= 0).all()
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-5)
    assert ((np.asarray(pre) > 0) & (np.asarray(pre) < 1)).all()
    assert ((np.asarray(post) > 0) & (np.asarray(post) < 2)).all()


def test_seeded_maps_are_neither_the_identity_nor_uniform(tiny):
    model, params = tiny
    hc = jax.tree_util.tree_map(lambda x: x[1], params["layers"]["hc_mlp"])
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 7, 64))
    res = np.asarray(M._hc_maps(hc, x, model.config)[2])
    diag = res[np.arange(4), np.arange(4)]
    assert 0.3 < diag.mean() < 0.95 and res.min() > 1e-4
    assert np.abs(res[..., 0] - res[..., 1]).max() > 1e-3   # per token


# ----------------------------------------------------------------- rotary
def test_yarn_blends_between_the_correction_dimensions():
    rs = get_config("xing4-29b-a4b").rope_scaling
    plain = rope_frequencies(64, 10000.0)
    yarn = rope_frequencies(64, 10000.0, rs)
    ratio = yarn / plain
    np.testing.assert_allclose(ratio[:11], 1.0)          # fast pairs: kept
    np.testing.assert_allclose(ratio[23:], 1 / 64)       # slow: interpolated
    assert (np.diff(ratio[10:24]) < 0).all()             # the ramp between


# ----------------------------------------------------- the round's record
def test_attention_work_counts_pairs_and_decode_contexts():
    """A 5-token chunk on 10 cached tokens attends 11 + 12 + 13 + 14 + 15
    pairs; a fresh 3-token prompt 1 + 2 + 3; two one-token chunks read their
    whole contexts, their own token included."""
    def desc(n):
        return SequenceDescriptor(uid=0, n_cached=n)

    work = attention_work([desc(10), desc(0), desc(7), desc(0)],
                          [5, 3, 1, 1])
    assert work == (65 + 6, 8 + 1, 8 + 1, 8 + 1)      # no atoms: the rows'
    assert attention_work([], []) == (0, 0, 0, 0)
    # atoms of 4 rows under steps of 8 keys, one-row tiles under steps of
    # 16: the 5-token chunk is atoms that see 14 and 15 keys (2 steps
    # each), the 3-token prompt one that sees 3 (1 step); the one-row
    # tiles see 8 and 1 (a step each)
    assert attention_work([desc(10), desc(0), desc(7), desc(0)],
                          [5, 3, 1, 1], 4, (8, 16))[2:] == (
        16 + 16 + 8 + 16 + 16, 14 + 15 + 3 + 8 + 1)
    # a context that fills its steps pays nothing; one key more, a step
    assert attention_work([desc(31), desc(32)], [1, 1], 4, (8, 16))[2:] == (
        32 + 48, 32 + 33)


def test_the_round_record_carries_both_counts(tiny):
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    eng = SERVING.engine_of(*tiny)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(1, list(range(1, 8)), 4)              # 7 tokens
    sess.step()
    sess.submit(2, list(range(1, 21)), 4)             # 20: chunks 15 + 5
    sess.step()
    sess.step()
    sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    assert [(d["program"], d["attn_pairs"], d["dec_ctx_tokens"])
            for d in rounds] == [
        ("ragged_forward", 28, 0),
        ("ragged_forward", 15 * 16 // 2, 8),          # 15 rows + one decode
        ("ragged_forward", 5 * 15 + 15, 9),
        ("decode_forward", 0, 10 + 21)]
    # the xla attention takes no atoms: the one-row tiles alone walk steps,
    # of what the kernel's rule gives this pool (16 blocks of 16 keys)
    assert eng._kv_step_keys[1] == 16 * pa._kv_pages_per_step(
        1, 4, 1, eng.kv.k.shape[-1], 16, 4, True)
    step = eng._kv_step_keys[1]
    assert [(d["kv_step_keys"], d["kv_tile_keys"]) for d in rounds] == [
        (0, 0), (step, 8), (step, 9), (2 * step, 10 + 21)]
    sess.close()


def test_the_round_record_counts_the_atoms_steps(tiny, monkeypatch):
    """With the kernel's atoms (8 rows here) under a step of 2 blocks of
    16 keys: a 20-token prompt's chunks of 16 and 4 rows are atoms that see
    8, 16 and then 20 keys."""
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    monkeypatch.setattr(pa, "_kv_pages_per_step", lambda *a: 2 if a[-1]
                        else 1)
    eng = SERVING.engine_of(*tiny, prefill_attn="kernel_interpret",
                    decode_attn="pallas_interpret", atom_q_size=8)
    assert eng._kv_step_keys == (32, 32)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(2, list(range(1, 21)), 2)             # 20: chunks 16 + 4
    while not sess.idle:
        sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round" and r["data"]["program"]]
    assert [(d["atoms"], d["decode_rows"], d["kv_step_keys"],
             d["kv_tile_keys"]) for d in rounds] == [
        (2, 0, 32 + 32, 8 + 16), (1, 0, 32, 20), (0, 1, 32, 21)]
    sess.close()


def test_the_training_forward_refuses_what_only_serving_runs(tiny):
    model, params = tiny
    with pytest.raises(NotImplementedError, match="serving path only"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        dataclasses.replace(get_config("tiny"), first_k_dense_replace=1)


# ------------------------------------------------- a stream that ends early
@pytest.fixture(scope="module")
def ending(tiny):
    return stream_ends.family(SERVING.engine_of(*tiny, max_context=48,
                                        num_blocks=12))


@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    stream_ends.check(ending, driver, end)
