"""``launch_ahead_pct``: the reader on the recorded v5e rounds
(``data/decode_sat_v5e_rounds.json``) with the field the ``round`` record
gained; on records that lack it, as every commit before the one that
launches ahead writes them; and on a real session at tiny size, where every
forward but the first after idle is launched before the read-back."""
import copy
import json
import time
from pathlib import Path

import pytest

from benchmark import spec

DATA = Path(__file__).parent / "data"
READ = spec.Bench().reader("launch_ahead_pct")


def _recorded(ahead=None, launched=None):
    """The recorded rounds, ``ahead[i]`` written into the window's ``i``-th
    record (None: the parent's records, without the field)."""
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    stages = copy.deepcopy(fx["stages"])
    window = (fx["rounds"][2][1], fx["rounds"][6][1])   # rounds 104-107
    inside = [s["data"] for s in stages if window[0] < s["data"]["t1"]]
    for k, d in enumerate(inside):
        if ahead is not None:
            d["ahead"] = ahead[k]
        if launched is not None and not launched[k]:
            d["program"], d["launch_t"] = None, None
    assert len(inside) == 4
    return {"rounds": [tuple(r) for r in fx["rounds"]], "stages": stages,
            "window": window}


@pytest.mark.parametrize("ahead, launched, want", [
    ((1, 1, 1, 1), None, 100.0),
    ((0, 1, 1, 1), None, 75.0),             # the first round after idle
    ((0, 0, 0, 0), None, 0.0),
    ((1, 1, 1, 0), (1, 1, 1, 0), 100.0),    # a round that launched nothing
], ids=["every_round", "first_after_idle", "in_order", "nothing_launched"])
def test_the_recorded_rounds(ahead, launched, want):
    assert READ(_recorded(ahead, launched)) == pytest.approx(want)


@pytest.mark.parametrize("case", ["no_field", "no_records", "no_launch"])
def test_nothing_to_read_is_none(case, capsys):
    obs = _recorded(None if case == "no_field" else (1, 1, 1, 1),
                    (0, 0, 0, 0) if case == "no_launch" else None)
    if case == "no_records":
        obs["stages"] = []
    assert READ(obs) is None
    assert ("no reading" in capsys.readouterr().err) == (case == "no_records")


def test_a_tiny_session_launches_every_forward_but_the_first_ahead():
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.inference.v2 import (
        InferenceEngineV2, ServingPolicyConfig, ServingSession)
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny", dtype="float32")
    eng = InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=8,
        max_context=64, max_tokens_per_batch=18, max_sequences=4)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    rounds = []

    def step():
        t0 = time.perf_counter()
        sess.step()
        rounds.append((t0, time.perf_counter(), len(eng.seqs), 0))

    t_open = time.perf_counter()
    sess.submit(1, [1, 2, 3], 6)
    sess.submit(2, [4, 5, 6, 7, 8], 4)
    step()
    sess.submit(3, list(range(10, 30)), 3)      # two chunks beside decode rows
    while not sess.idle:
        step()
    obs = {"rounds": rounds, "stages": sess.drain_trace(),
           "window": (t_open, rounds[-1][1])}
    stats = sess.stats()
    sess.close()
    launched = [s["data"] for s in obs["stages"]
                if s["data"].get("stage") == "round" and s["data"]["program"]]
    assert [d["ahead"] for d in launched] == [0] + [1] * (len(launched) - 1)
    assert READ(obs) == pytest.approx(
        100.0 * (len(launched) - 1) / len(launched))
    assert stats["launched_ahead"] == len(launched) - 1
    assert stats["speculative_rows"] == 0
    # from the second round on, as a saturated cell's window: every one
    obs["window"] = (rounds[0][1], rounds[-1][1])
    assert READ(obs) == 100.0


def test_the_metric_is_declared_for_the_two_cells_that_claim():
    bench = spec.Bench()
    entry, = [m for m in bench.doc["per_layer"]
              if m["name"] == "launch_ahead_pct"]
    assert {"phi2-decode-sat", "olmoe-chat-sat"} <= set(entry["workloads"])
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "serve_tok_s", "program_span", "serve engine")
    assert not bench.problems()
