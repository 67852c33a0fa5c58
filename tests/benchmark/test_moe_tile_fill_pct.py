"""``moe_tile_fill_pct``: the reader on the recorded v5e rounds
(``data/decode_sat_v5e_rounds.json``) given the fields a sparse-expert
program's ``round`` record gained; on records that lack them, as every commit
before the one whose grouped GEMM has tiles of its own writes them; and on a
real session at tiny size."""
import copy
import json
import time
from pathlib import Path

import pytest

from benchmark import spec

DATA = Path(__file__).parent / "data"
READ = spec.Bench().reader("moe_tile_fill_pct")


def _recorded(tiles=None, tile_rows=16, rows=None):
    """The recorded rounds as a sparse-expert program would have written
    them: ``tiles[i]`` the visits of the forward the window's ``i``-th record
    launched (on the record AFTER it, as they ride behind the next round's
    tokens); ``rows[i]`` likewise where the program counts them. None: the
    parent's records."""
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    stages = copy.deepcopy(fx["stages"])
    window = (fx["rounds"][2][1], fx["rounds"][6][1])   # rounds 104-107
    records = sorted((s["data"] for s in stages), key=lambda d: d["t0"])
    inside = [d for d in records if window[0] < d["t1"]][:4]
    # the record behind the window's last, which brings its counts
    last = copy.deepcopy(records[-1])
    last.update(round=last["round"] + 1, t0=last["t1"],
                t1=2 * last["t1"] - last["t0"])
    stages.append({"name": "serve/stage", "data": last})
    by_round = {d["round"]: d for d in records + [last]}
    for k, d in enumerate(inside):
        if tiles is None:
            continue
        d.update(moe_tile_rows=tile_rows, moe_rows_a_token=8 * 10)
        nxt = by_round[d["round"] + 1]
        nxt["moe_tiles"] = tiles[k]
        if rows is not None:
            nxt["moe_rows"] = rows[k]
    return {"rounds": [tuple(r) for r in fx["rounds"]], "stages": stages,
            "window": window}, inside


def test_the_recorded_rounds():
    obs, inside = _recorded(tiles=(600, 610, 620, 630))
    rows = sum(d["tokens"] * 80 for d in inside)
    assert READ(obs) == pytest.approx(100.0 * rows / (2460 * 16))
    # a program that counts its rows (it holds a share of the experts)
    obs, _ = _recorded(tiles=(100, 100, 100, 100), tile_rows=32,
                       rows=(640, 320, 160, 160))
    assert READ(obs) == pytest.approx(100.0 * 1280 / (400 * 32))


@pytest.mark.parametrize("case", ["no_field", "no_records", "no_visit"])
def test_nothing_to_read_is_none(case):
    obs, _ = _recorded(None if case == "no_field" else (0, 0, 0, 0)
                       if case == "no_visit" else (5, 5, 5, 5))
    if case == "no_records":
        obs["stages"] = []
    assert READ(obs) is None


def test_a_tiny_session_fills_its_tiles(monkeypatch):
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.inference.v2 import (
        InferenceEngineV2, ServingPolicyConfig, ServingSession)
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import grouped_gemm

    monkeypatch.setattr(grouped_gemm, "ROW_TILES", (2, 4))
    model = build_model("tiny-moe", num_experts=8, num_experts_per_tok=2,
                        dtype="float32")
    eng = InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=8,
        max_context=64, max_tokens_per_batch=16, max_sequences=4,
        prefill_attn="xla", decode_attn="xla")
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    rounds = []
    t_open = time.perf_counter()
    sess.submit(1, [1, 2, 3], 6)
    sess.submit(2, list(range(10, 30)), 4)
    while not sess.idle:
        t0 = time.perf_counter()
        sess.step()
        rounds.append((t0, time.perf_counter(), len(eng.seqs), 0))
    obs = {"rounds": rounds, "stages": sess.drain_trace(),
           "window": (t_open, rounds[-1][1])}
    sess.close()
    records = [s["data"] for s in obs["stages"]
               if s["data"].get("stage") == "round"]
    after = {d["round"] - 1: d for d in records}
    counted = [(d["tokens"] * 4, after[d["round"]]["moe_tiles"]
                * d["moe_tile_rows"]) for d in records
               if d["program"] and d["round"] in after]
    assert len(counted) >= 6
    fill = READ(obs)
    assert fill == pytest.approx(100.0 * sum(r for r, _ in counted)
                                 / sum(t for _, t in counted))
    # one or two rows an expert in tiles of 2 and 4: between 1 / 4 and all
    assert 25.0 <= fill <= 100.0


def test_the_metric_is_declared_for_the_one_cell_that_claims():
    bench = spec.Bench()
    # no place in the list is held and no other cell is shut out: a later PR
    # appends entries behind it and cells to its ``workloads``
    entry, = [m for m in bench.doc["per_layer"]
              if m["name"] == "moe_tile_fill_pct"]
    assert "olmoe-chat-sat" in entry["workloads"]
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "serve_tok_s", "program_counter", "kernels")
    assert not bench.problems()
