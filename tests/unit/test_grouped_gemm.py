"""The grouped GEMM whose row tile fits the expert (``ops/grouped_gemm.py``):
the kernel in interpret mode against ``jax.lax.ragged_dot`` and a float32
``jnp`` reference; ``moe_mlp_nodrop`` end to end with the kernel forced in
place of ``ragged_dot``; and the counter of its row-tile visits
(``moe_tiles``) from the device to the ``round`` record, the report and the
benchmark's reader."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.model import moe_tile_rows
from deepspeedsyclsupport_tpu.inference.v2.supervisor import journal_path
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import reqtrace
from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg
from deepspeedsyclsupport_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------- the kernel
def _case(sizes, tail, k=64, n=128, layers=2, seed=0):
    """Rows sorted by group (``sizes`` rows each, ``tail`` rows in none
    behind them) and stacked weights, in bf16."""
    rng = np.random.default_rng(seed)
    sizes = np.asarray(sizes, np.int32)
    e, m = len(sizes), int(sizes.sum()) + tail
    group = np.concatenate([np.repeat(np.arange(e), sizes),
                            np.full(tail, e)]).astype(np.int32)

    def bf16(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    return {"sizes": jnp.asarray(sizes), "group": jnp.asarray(group),
            "x": bf16(m, k), "w_gate": bf16(layers, e, k, n, scale=0.1),
            "w_up": bf16(layers, e, k, n, scale=0.1),
            "w_down": bf16(layers, e, n, k, scale=0.1)}


def _kernel(c, tile, layer=0, stack=True):
    """gate, up, activation, down through the kernel, in sorted order."""
    tiles = gg.tile_rows(c["sizes"], c["group"], tile)
    w = {n: c[n] if stack else c[n][layer]
         for n in ("w_gate", "w_up", "w_down")}
    kw = dict(layer=layer if stack else 0, interpret=True)
    mid = gg.grouped_glu(c["x"][tiles.src], w["w_gate"], w["w_up"], tiles,
                         act=jax.nn.silu, **kw)
    return gg.grouped_matmul(mid, w["w_down"], tiles, **kw)[tiles.dest]


def _ragged_dot(c, layer):
    def grouped(rows, w):
        return jax.lax.ragged_dot(rows, w[layer], c["sizes"])

    mid = jax.nn.silu(grouped(c["x"], c["w_gate"])) \
        * grouped(c["x"], c["w_up"])
    return grouped(mid, c["w_down"])


def _float32(c, layer):
    """Every row through its own expert's matrices, nothing rounded."""
    n = int(c["sizes"].sum())
    x = np.asarray(c["x"], np.float32)[:n]
    g = np.asarray(c["group"])[:n]
    wg, wu, wd = (np.asarray(c[w], np.float32)[layer][g]
                  for w in ("w_gate", "w_up", "w_down"))
    gate = np.einsum("rk,rkn->rn", x, wg)
    mid = gate / (1 + np.exp(-gate)) * np.einsum("rk,rkn->rn", x, wu)
    return np.einsum("rn,rnk->rk", mid, wd)


CASES = {
    # rows an expert: under, at and over a tile; an expert with no row
    "one_row": dict(sizes=[1, 1, 1, 1], tail=0, tile=16),
    "four_rows": dict(sizes=[4, 3, 5, 4], tail=0, tile=16),
    "at_a_tile": dict(sizes=[16, 32, 16, 16], tail=0, tile=16),
    "48_rows_tile_64": dict(sizes=[48, 50, 46, 48], tail=0, tile=64),
    "48_rows_tile_32": dict(sizes=[48, 50, 46, 48], tail=0, tile=32),
    "200_rows": dict(sizes=[200, 7, 130, 47], tail=0, tile=64),
    "no_row_in_the_middle": dict(sizes=[5, 0, 0, 48, 0, 1], tail=0, tile=16),
    "no_row_first_and_last": dict(sizes=[0, 9, 17, 0], tail=0, tile=16),
    "no_row_at_all": dict(sizes=[0, 0, 0, 0], tail=24, tile=16),
    # dead and not-held rows sort behind the last group
    "dead_tail": dict(sizes=[3, 0, 20, 1], tail=40, tile=16),
    # the weights: one layer's leaf, a stack read at a static or traced layer
    "one_layer_leaf": dict(sizes=[4, 3, 5, 4], tail=3, tile=16, stack=False,
                           layer=1),
    "stack_static_layer": dict(sizes=[4, 3, 5, 4], tail=3, tile=16, layer=1),
    "stack_traced_layer": dict(sizes=[4, 3, 5, 4], tail=3, tile=16, layer=1,
                               traced=True),
    # K x N not a multiple of the tile: whole blocks
    "narrow_columns": dict(sizes=[4, 30, 5, 4], tail=2, tile=16, k=40, n=72),
    # N in several column blocks (a budget only two 128-wide ones fit)
    "column_blocks": dict(sizes=[4, 30, 5, 4], tail=2, tile=16, k=72, n=384,
                          budget=2 * 2 * 72 * 2 * 128),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_the_kernel_is_ragged_dot(case, monkeypatch):
    case = dict(case)
    tile, layer = case.pop("tile"), case.pop("layer", 0)
    stack, traced = case.pop("stack", True), case.pop("traced", False)
    if "budget" in case:
        monkeypatch.setattr(gg, "_WEIGHT_VMEM_BUDGET", case.pop("budget"))
        assert gg.col_tile(case["k"], case["n"], 2, 2) == 128
    c = _case(**case)
    if traced:
        got = jax.jit(lambda l: _kernel(c, tile, l))(jnp.int32(layer))
    else:
        got = _kernel(c, tile, layer, stack)
    n = int(c["sizes"].sum())
    got = np.asarray(got, np.float32)
    assert got.shape == c["x"].shape
    if not n:
        return                        # nothing had an expert: nothing read
    want, exact = np.asarray(_ragged_dot(c, layer), np.float32)[:n], \
        _float32(c, layer)
    scale = np.abs(exact).max()
    # bf16 keeps 8 bits: the kernel rounds once after the float32 epilogue,
    # ragged_dot's path three times
    assert np.abs(got[:n] - exact).max() <= 0.01 * scale
    assert np.abs(got[:n] - want).max() <= 0.02 * scale
    assert np.abs(got[:n] - exact).max() \
        <= np.abs(want - exact).max() + 0.004 * scale


def test_the_row_tile_follows_the_shape():
    # the six serving forwards of the three sparse cells
    assert [gg.row_tile(*s) for s in (
        (32 * 8, 64), (64 * 6, 160), (16 * 4, 64),
        (768 * 8, 64), (768 * 6, 160), (768 * 4, 64))] \
        == [16, 16, 16, 128, 32, 64]
    assert gg.row_tile(10 ** 6, 8) == 128
    # whole N where two double-buffered blocks fit, else a divisor of it
    assert gg.col_tile(2048, 1024, 2, 2) == 1024
    assert gg.col_tile(5120, 1536, 2, 2) in (512, 768)
    assert gg.col_tile(1536, 5120, 1, 2) == 5120
    assert gg.col_tile(64, 72, 1, 2) == 72


def test_every_group_starts_on_a_tile_and_tiles_past_the_last_repeat_it():
    sizes = jnp.asarray([3, 0, 20, 1], jnp.int32)
    group = jnp.asarray([0] * 3 + [2] * 20 + [3] + [4] * 8, jnp.int32)
    tiles = gg.tile_rows(sizes, group, 16)
    assert int(tiles.live[0]) == 4 == int(gg.tile_visits(sizes, 16))
    assert tiles.group.tolist() == [0, 2, 2, 3] + [3] * (32 // 16 + 4 - 4)
    dest = tiles.dest.tolist()
    assert dest[:3] == [0, 1, 2] and dest[3:23] == list(range(16, 36))
    assert dest[23] == 48
    src = np.asarray(tiles.src)
    assert (src[np.asarray(dest[:24])] == np.arange(24)).all()
    assert src.min() >= 0 and src.max() < 32
    assert int(gg.tile_visits(jnp.stack([sizes, sizes]), 2)) \
        == 2 * (2 + 10 + 1)


def test_a_stack_of_another_dtype_is_refused():
    c = _case([4, 4], 0)
    tiles = gg.tile_rows(c["sizes"], c["group"], 16)
    with pytest.raises(ValueError, match="activations' dtype"):
        gg.grouped_matmul(c["x"][tiles.src],
                          c["w_gate"].astype(jnp.float32), tiles,
                          interpret=True)
    # one layer's leaf is cast, as ragged_dot's path casts it
    out = gg.grouped_matmul(c["x"][tiles.src],
                            c["w_gate"][0].astype(jnp.float32), tiles,
                            interpret=True)
    assert out.dtype == jnp.bfloat16


# ------------------------------------------------ moe_mlp_nodrop end to end
@pytest.fixture
def interpreted(monkeypatch):
    """The TPU's path off the TPU: the kernel, interpreted."""
    def force():
        monkeypatch.setattr(gg, "default_impl", lambda: "pallas_interpret")
    return force


MODELS = {
    "every_expert": dict(),
    "a_share": dict(num_experts_held=2, first_expert_held=4),
    "a_share_at_the_end": dict(num_experts_held=3, first_expert_held=5),
    "shared_expert": dict(n_shared_experts=1),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["leaf", "stack"])
@pytest.mark.parametrize("over", MODELS.values(), ids=MODELS.keys())
def test_moe_mlp_nodrop_with_the_kernel_is_the_ragged_dot_path(
        over, stacked, interpreted):
    model = build_model("tiny-moe", num_experts=8, num_experts_per_tok=2,
                        dtype="bfloat16", **over)
    cfg = model.config
    layers = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        model.init_params(jax.random.PRNGKey(0))["layers"]["moe"])
    p = jax.tree_util.tree_map(lambda a: a[1], layers)
    layer = 0
    if stacked:
        p = {**p, **{n: layers[n] for n in ("w_gate", "w_up", "w_down")}}
        layer = jnp.int32(1)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden_size),
                          jnp.bfloat16)
    live = jnp.arange(24) < 19
    run = jax.jit(lambda l: moe.moe_mlp_nodrop(p, x, cfg, live, l))
    want, routed = run(layer)
    interpreted()
    got, routed_k = jax.jit(
        lambda l: moe.moe_mlp_nodrop(p, x, cfg, live, l))(layer)
    assert routed_k.tolist() == routed.tolist()
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert (got[19:] == want[19:]).all()       # a dead row: the shared sum
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


# -------------------------------------------------------------- the counter
@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles a tiny model can fill or straddle: its decode step takes 2
    rows a tile, its chunk forward 4."""
    monkeypatch.setattr(gg, "ROW_TILES", (2, 4))


def _engine(**over):
    model = build_model("tiny-moe", num_experts=8, num_experts_per_tok=2,
                        dtype="float32", **over)
    return InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=8,
        max_context=64, max_tokens_per_batch=16, max_sequences=4,
        prefill_attn="xla", decode_attn="xla")


def _host_visits(load, tile):
    return int(-(-np.asarray(load) // tile).sum())


@pytest.mark.parametrize("over", [dict(), dict(num_experts_held=2,
                                               first_expert_held=4)],
                         ids=["every_expert", "a_share"])
def test_moe_tiles_are_the_visits_counted_from_the_same_group_sizes(
        over, small_tiles):
    from deepspeedsyclsupport_tpu.inference.sampling import SamplingParams

    eng = _engine(**over)
    cfg = eng.model.config
    assert [moe_tile_rows(cfg, t) for t in (4, 16)] == [2, 4]
    held = cfg.held_experts
    key = jax.random.PRNGKey(0)
    eng.put([1, 2], [[1, 2, 3], list(range(4, 15))])   # a chunk forward
    first = eng.moe_stats()["load"][:, held]
    tail = eng.moe_tail(reqtrace.MOE_TAIL_FIELDS)
    assert len(tail) == len(eng.moe_tail()) + 1
    toks, counted = eng.sample_drained([1, 2], key, SamplingParams(),
                                       tail=tail)
    counted = dict(zip(reqtrace.MOE_TAIL_FIELDS, counted))
    assert counted["moe_tiles"] == _host_visits(first, 4)
    assert counted["moe_touched"] == (first > 0).sum()
    assert counted["moe_touched"] <= counted["moe_tiles"] <= first.sum()
    if over:
        assert counted["moe_rows"] == first.sum()
    eng.put([1, 2], [[int(t)] for t in toks])          # a decode step
    second = eng.moe_stats()["load"][:, held] - first
    assert int(eng.kv.moe.tiles) == _host_visits(second, 2)


def test_the_round_record_carries_the_tiles_and_a_dense_one_does_not(
        small_tiles, tmp_path):
    jdir = str(tmp_path / "journal")
    eng = _engine()
    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", journal_path=journal_path(jdir)))
    for uid, prompt, budget in ((1, [1, 2, 3], 9), (2, list(range(20)), 6)):
        assert sess.submit(uid, prompt, budget) == "admitted"
    while not sess.idle:
        sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    sess.close()
    cfg = eng.model.config
    launched = [d for d in rounds if d["program"]]
    assert {d["program"]: d["moe_tile_rows"] for d in launched} \
        == {"ragged_forward": 4, "decode_forward": 2}
    assert all(d["moe_rows_a_token"] == 2 * cfg.num_layers for d in launched)
    # a forward's count is on the record after its own
    after = {d["round"] - 1: d for d in rounds}
    for d in launched:
        tiles, rows = after[d["round"]]["moe_tiles"], d["tokens"] * 4
        assert after[d["round"]]["moe_touched"] <= tiles <= rows
        assert rows <= tiles * d["moe_tile_rows"]
    # every row the engine ever routed lies in one of the tiles it counted
    assert sum(d["tokens"] for d in launched) * 4 \
        == eng.moe_stats()["load"].sum()
    # the report: fill and the tiles an expert's weights served
    table = reqtrace.round_phases(reqtrace.load_root(jdir)[0])
    decode = table["programs"]["decode_forward"]
    assert decode["moe_tile_rows"] == 2 and decode["moe_tiles"] > 0
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--requests", jdir], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    head, = [ln.split() for ln in out.stdout.splitlines()
             if ln.strip().startswith("launched")]
    assert head[-3:] == ["tile-rows", "tile-fill", "tiles/expert"]
    line, = [ln.split() for ln in out.stdout.splitlines()
             if ln.strip().startswith("decode_forward")]
    fill = 100 * decode["tokens"] * 4 / (decode["moe_tiles"] * 2)
    assert line[-3] == "2" and line[-2] == f"{fill:.1f}%"
    assert float(line[-1]) == pytest.approx(
        decode["moe_tiles"] / decode["moe_touched"], abs=0.006)
    # a dense model: no tail, no field
    model = build_model("tiny", dtype="float32")
    dense = InferenceEngineV2(model, model.init_params(), dtype=jnp.float32,
                              block_size=8, max_context=64,
                              max_tokens_per_batch=16, max_sequences=4)
    assert dense.moe_tail(reqtrace.MOE_TAIL_FIELDS) is None
    sess = ServingSession(dense, ServingPolicyConfig(admission="none"))
    assert sess.submit(1, [1, 2, 3], 4) == "admitted"
    while not sess.idle:
        sess.step()
    records = [r["data"] for r in sess.drain_trace()]
    sess.close()
    assert not any(f in d for d in records for f in
                   ("moe_tiles",) + reqtrace.MOE_STATIC_FIELDS)
