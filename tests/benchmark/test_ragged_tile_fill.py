"""``ragged_tile_fill_pct``: the reader on the recorded v5e rounds
(``data/decode_sat_v5e_rounds.json``, whose one ``ragged_forward`` round
carries 31 decoding sequences and a 96-token prompt) with the two fields
the ``round`` record gained, by either layout of that round; on records
that lack them; and on a real session at tiny size through the interpreted
kernels."""
import copy
import json
import time
import types
from pathlib import Path

import pytest

from benchmark import spec

DATA = Path(__file__).parent / "data"
READ = spec.Bench().reader("ragged_tile_fill_pct")
# (atoms, decode_rows) of the recorded mixed round: 127 tokens in 32 slots
LAYOUTS = {"a_row_a_decode_token": ((1, 31), 100 * 127 / (128 + 31)),
           "every_chunk_in_atoms": ((32, 0), 100 * 127 / (32 * 128))}


def _recorded(tiles=None):
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    stages = copy.deepcopy(fx["stages"])
    for s in stages:
        d = s["data"]
        if tiles is not None:
            ragged = d["program"] == "ragged_forward"
            d["atoms"], d["decode_rows"] = tiles if ragged \
                else (0, d["tokens"])
    engine = types.SimpleNamespace(
        config=types.SimpleNamespace(atom_q_size=128))
    return {"rounds": [tuple(r) for r in fx["rounds"]], "stages": stages,
            "window": (fx["rounds"][2][1], fx["rounds"][6][1]),
            "engine": engine}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_recorded_mixed_round_by_either_layout(layout):
    tiles, want = LAYOUTS[layout]
    assert READ(_recorded(tiles)) == pytest.approx(want)
    assert 0 < want <= 100


@pytest.mark.parametrize("case", ["no_fields", "no_ragged_round", "no_atoms",
                                  "no_records"])
def test_nothing_to_read_is_none(case, capsys):
    obs = _recorded(None if case == "no_fields" else (1, 31))
    if case == "no_ragged_round":        # the window ends before round 106
        obs["window"] = (obs["rounds"][2][1], obs["rounds"][4][1])
    elif case == "no_atoms":             # an attention that takes no atoms
        obs = _recorded((0, 31))
    elif case == "no_records":
        obs["stages"] = []
    assert READ(obs) is None
    assert ("no reading" in capsys.readouterr().err) == (case == "no_records")


def test_a_tiny_session_through_the_kernels():
    """Two short prompts, then a 20-token prompt while they decode: the
    window's three ``ragged_forward`` rounds carry 8 + 18 + 6 tokens in
    2 + 2 + 1 atoms of 8 rows and 0 + 2 + 2 one-row tiles."""
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.inference.v2 import (
        InferenceEngineV2, ServingPolicyConfig, ServingSession)
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny", dtype="float32")
    eng = InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=8,
        max_context=64, max_tokens_per_batch=18, max_sequences=4,
        prefill_attn="kernel_interpret", decode_attn="pallas_interpret",
        atom_q_size=8)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    rounds = []

    def step():
        t0 = time.perf_counter()
        sess.step()
        rounds.append((t0, time.perf_counter(), len(eng.seqs), 0))

    t_open = time.perf_counter()
    sess.submit(1, [1, 2, 3], 8)
    sess.submit(2, [4, 5, 6, 7, 8], 8)
    step()
    sess.submit(3, list(range(10, 30)), 8)
    for _ in range(4):
        step()
    obs = {"rounds": rounds, "stages": sess.drain_trace(),
           "window": (t_open, rounds[-1][1]), "engine": eng}
    sess.close()
    ragged = [s["data"] for s in obs["stages"]
              if s["data"].get("program") == "ragged_forward"]
    assert [(d["tokens"], d["atoms"], d["decode_rows"]) for d in ragged] == [
        (8, 2, 0), (18, 2, 2), (6, 1, 2)]
    assert READ(obs) == pytest.approx(100 * 32 / (5 * 8 + 4))
