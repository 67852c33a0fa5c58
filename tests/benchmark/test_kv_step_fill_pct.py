"""``kv_step_fill_pct``: the reader on the recorded v5e rounds
(``data/decode_sat_v5e_rounds.json``) given the two counts the ``round``
record gained with the step of several KV blocks; on records that lack them,
as every commit before that one writes them; and on a real session over a
latent pool at tiny size, both entries of the kernel interpreted."""
import copy
import json
import time
from pathlib import Path

import pytest

from benchmark import spec

DATA = Path(__file__).parent / "data"
BENCH = spec.Bench()
READ = BENCH.reader("kv_step_fill_pct")


def _recorded(counts=None):
    """The recorded rounds with ``counts[i]`` = (``kv_tile_keys``,
    ``kv_step_keys``) on the window's ``i``-th record; None: the parent's
    records."""
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    stages = copy.deepcopy(fx["stages"])
    window = (fx["rounds"][2][1], fx["rounds"][6][1])   # rounds 104-107
    records = sorted((s["data"] for s in stages), key=lambda d: d["t0"])
    inside = [d for d in records if window[0] < d["t1"]][:4]
    for d, (keys, steps) in zip(inside, counts or ()):
        d.update(kv_tile_keys=keys, kv_step_keys=steps)
    return {"rounds": [tuple(r) for r in fx["rounds"]], "stages": stages,
            "window": window}


def test_the_recorded_rounds():
    # 32 one-row tiles a round under steps of 512 keys, then a mixed round
    obs = _recorded([(9000, 32 * 512), (9032, 32 * 512), (9064, 32 * 512),
                     (30000, 31744)])
    assert READ(obs) == pytest.approx(
        100.0 * (9000 + 9032 + 9064 + 30000) / (3 * 32 * 512 + 31744))
    # steps of one block of one key: all of it context
    assert READ(_recorded([(7, 7)] * 4)) == 100.0
    # found by the name every cell lists it under, it reads the same
    assert BENCH.reader("kv_step_fill_pct")(obs) == READ(obs)


@pytest.mark.parametrize("case", ["no_field", "no_records", "no_step"])
def test_nothing_to_read_is_none(case):
    obs = _recorded(None if case == "no_field" else [(0, 0)] * 4
                    if case == "no_step" else [(5, 8)] * 4)
    if case == "no_records":
        obs["stages"] = []
    assert READ(obs) is None


def test_a_tiny_latent_session_pays_its_steps_tails(monkeypatch):
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.inference.v2 import (
        InferenceEngineV2, ServingPolicyConfig, ServingSession)
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    # steps of 4 blocks of 8 keys under both entries
    monkeypatch.setattr(pa, "_kv_pages_per_step",
                        lambda *a: 4 if a[-1] else 1)
    model = build_model(
        "xing4-29b-a4b", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=3, first_k_dense_replace=1,
        num_heads=4, num_kv_heads=4, head_dim=24, vocab_size=512,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
        num_experts_per_tok=3, max_seq_len=256, dtype="float32")
    eng = InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=8,
        max_context=128, max_tokens_per_batch=16, max_sequences=4,
        atom_q_size=8, prefill_attn="kernel_interpret",
        decode_attn="pallas_interpret")
    assert eng._kv_step_keys == (32, 32)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    rounds = []
    t_open = time.perf_counter()
    sess.submit(1, [1, 2, 3], 6)
    sess.submit(2, list(range(10, 50)), 4)
    while not sess.idle:
        t0 = time.perf_counter()
        sess.step()
        rounds.append((t0, time.perf_counter(), len(eng.seqs), 0))
    obs = {"rounds": rounds, "stages": sess.drain_trace(),
           "window": (t_open, rounds[-1][1])}
    sess.close()
    records = [s["data"] for s in obs["stages"]
               if s["data"].get("stage") == "round" and s["data"]["program"]]
    assert len(records) >= 6
    for d in records:          # whole steps, never fewer keys than the tiles'
        assert d["kv_step_keys"] % 32 == 0
        assert 0 < d["kv_tile_keys"] <= d["kv_step_keys"] \
            < d["kv_tile_keys"] + 32 * (d["atoms"] + d["decode_rows"])
    fill = READ(obs)
    assert fill == pytest.approx(
        100.0 * sum(d["kv_tile_keys"] for d in records)
        / sum(d["kv_step_keys"] for d in records))
    # contexts of 3 to 44 keys in steps of 32: well under full
    assert 20.0 < fill < 90.0


def test_the_metric_is_declared_for_the_two_latent_cells():
    entry, = [m for m in BENCH.doc["per_layer"]
              if m["name"] == "kv_step_fill_pct"]    # ONE entry since PR 48
    assert {"xing4-docs-sat", "dsv2-answers-sat"} <= set(entry["workloads"])
    assert (entry["moves"], entry["source"], entry["layer"],
            entry["unit"], entry["better"]) == (
        "itl_p95_ms", "program_counter", "kernels", "%", "higher")
    assert not BENCH.problems()
