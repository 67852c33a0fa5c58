"""The ``brumby`` configuration, its cell and its readers: the file against
the catalog row and the program's preset; the family's counts against a hand
count; a tiny cell of the family driven on the CPU through ``tiny.drive``;
the three new readers on hand-made traces and on nothing."""
import json
import types

import numpy as np
import pytest

from benchmark import spec

CELL, CONFIG, MIX = "brumby-rollout-sat", "brumby-14b-d8", "rollout-mid-sat"
NEW = ["state_share_pct", "state_decode_roofline", "state_chunk_roofline"]
# the accepted readers that read something in this cell (my chip runs, PR
# 49) and that it joined at no entry in PR 62, once a cell might
JOINED = ["live_seqs_mean", "itl_p99_ms.moe", "round_p50_ms.moe",
          "share_ragged_rounds_pct.moe", "serve_program_gib.moe",
          "decode_fwd_ms.moe", "ragged_fwd_ms.moe", "serve_idle_pct.moe",
          "launch_ahead_pct", "ragged_row_fill_pct", "state_bytes_per_seq"]
# ... and the one that miscounts here (no layer caches a key) and waits to
# be mended before the cell joins it
NOT_JOINED = ["kv_bytes_per_token.tok"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
L = 2
HF = {"model_type": "brumby", "num_attention_heads": 4, "hidden_size": 64,
      "head_dim": 32, "intermediate_size": 96, "num_hidden_layers": L,
      "num_key_value_heads": 2, "vocab_size": 512, "rope_theta": 1000000,
      "rms_norm_eps": 1e-6, "sliding_window": None,
      "tie_word_embeddings": False}
OVERRIDES = {"hidden_size": 64, "intermediate_size": 96, "num_layers": L,
             "num_heads": 4, "num_kv_heads": 2, "head_dim": 32,
             "vocab_size": 512, "max_seq_len": 256, "retention_chunk_size": 8,
             "retention_half_life": [4.0, 64.0]}
ENGINE = {"max_context": 128, "max_sequences": 4, "block_size": 16,
          "max_tokens_per_batch": 32}
CALL = ('%{name}.1 = f32[8,4]{{1,0}} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "Brumby-14B-Base")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_cut_in_depth_alone():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["run"], cut["counts"]) == (40, 8, "layers")
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert set(cfg["assumed"]) >= {
        "degree", "scale", "gate", "normaliser", "qk_norm_and_rope", "state",
        "b_g", "weights", "dtype", "kv_pool", "prefix_cache"}
    assert "8320" in cfg["assumed"]["state"]
    assert cfg["engine"] == {"max_context": 8704, "max_sequences": 16,
                             "block_size": 64, "max_tokens_per_batch": 768}
    assert cfg["policy"] == spec.Bench().config("phi-2")["policy"]
    assert (cfg["path"], cfg["dtype"], cfg["preset"], cfg["overrides"]) \
        == ("serve", "bfloat16", "brumby-14b", {"num_layers": 8})
    assert "five pipeline stages" in cfg["deployment"]


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config
    from deepspeedsyclsupport_tpu.ops.retention import state_dim

    whole = get_config("brumby-14b")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cut = get_config("brumby-14b", num_layers=8)
    want = family.program_widths(spec.Bench().config(CONFIG))
    assert {k: getattr(cut, k) for k in want} == want
    assert (whole.num_layers, whole.max_seq_len, whole.use_bias,
            whole.mlp_type, whole.activation, whole.layer_pattern,
            whole.state_layers) == (40, 32768, False, "glu", "silu", None, 40)
    # ISSUE 49's count: a layer 330.3 M, embedding and head 1.556 B
    layer = (whole.param_count() - cut.param_count()) / 32
    assert layer == pytest.approx(330.3e6, rel=1e-3)
    assert cut.param_count() * 2 / 1e9 == pytest.approx(8.40, abs=0.01)
    # the state: 65 diagonals of 128, one array under 2^31 elements
    a = family.arch(spec.Bench().config(CONFIG))
    engine = spec.Bench().config(CONFIG)["engine"]
    dim = state_dim(128)
    one = 8 * (engine["max_sequences"] + 1) * 8 * 128 * dim
    assert (dim, one < 2**31) == (8320, True)
    held = 8 * (128 + 1) * dim * 4
    assert held / 1e6 == pytest.approx(34.3, abs=0.1)
    # at the least: S alone is ISSUE 49's 33.8 MB, with z 34.1
    assert family.retention_state_bytes(a) / 1e6 == pytest.approx(34.08,
                                                                  abs=0.01)
    assert held / family.retention_state_bytes(a) < 1.01
    total = cut.param_count() * 2 + 17 * 8 * held
    assert 0.70 * 16.9e9 < total < 0.85 * 16.9e9


def test_the_familys_counts_against_a_hand_count(family):
    a = family.arch(HF)
    f = 32 * 33 // 2
    assert family.features(a) == f
    assert family.retention_state_bytes(a) == 2 * f * 33 * 4
    assert family.retention_row_bytes(a) == (2 * 4 + 2 * 2) * 32 * 2 + 2 * 4
    quad = 4 * 32 * 4 * (8 * 9 / 2)
    write = 2 * 8 * 2 * f * 33
    assert family.retention_chunk_flops(a, 8, True) == quad + write
    assert family.retention_chunk_flops(a, 8, False) \
        == quad + write + 2 * 8 * 4 * f * 33
    layer = 64 * 32 * (2 * 4 + 2 * 2) + 64 * 2 + 3 * 64 * 96
    assert family.matmul_params(a) == L * layer + 64 * 512
    assert family.train_flops_per_token(a, 8) == 6 * (
        L * layer + 64 * 512) + 3 * 2 * 6 * f * 33 * L


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert reports >= {"start_to_chip_s", *NEW, *JOINED}
    assert not reports & set(NOT_JOINED)
    for m in bench.doc["per_layer"]:
        if m["name"] in (*NEW, *JOINED):
            assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
        if m["name"] in NEW:
            # a share of the busy time is better lower, a roofline higher
            assert (m["unit"], m["better"], m["source"]) == (
                "%", "lower" if m["name"].endswith("share_pct") else "higher",
                "device_trace")


def test_the_mix_is_the_issues_and_fits_the_context():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], len(pairs), mix["trace_seconds"]) \
        == ("closed", 16, 256, 10)
    assert mix["prompt_len"] == {"dist": "lognormal", "min": 128,
                                 "max": 8192, "median": 1024, "sigma": 0.8}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 384}
    assert mix["clients"] == cfg["max_sequences"]
    assert max(p + o for p, o in pairs) <= cfg["max_context"]
    assert 1200 < sum(p for p, _ in pairs) / 256 < 1700


# ------------------------------------------------------------ the tiny cell
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc, name = bench.doc, "tiny-brumby"
    cfg = {**HF, "source": "tests", "path": "serve", "preset": "brumby-14b",
           "overrides": OVERRIDES, "dtype": "float32", "engine": ENGINE,
           "policy": {"admission": "none", "preempt_policy": "requeue"}}
    (root / "extra" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": name, "source": "tests", "reduced": [],
                           "why": "tiny",
                           "file": f"extra/configs/{name}.json"})
    doc["workloads"].append({"name": f"{name}-cell", "chips": 1,
                             "config": name, "why": "tiny",
                             "traffic": "tiny-closed"})
    # the tiny cell lists what the real one does AND the reader that reads
    # something here but miscounts at the real sizes (NOT_JOINED)
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()) or m["name"] in NOT_JOINED:
            m["workloads"].append(f"{name}-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, f"{name}-cell", seed=2**31 + 49)


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
    from deepspeedsyclsupport_tpu.ops.retention import state_dim

    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    by_name = {x["name"]: x for x in spec.Bench().doc["per_layer"]}
    untraced = {n for n in (*JOINED, *NOT_JOINED)
                if by_name[n]["source"] != "device_trace"}
    assert untraced <= set(m), untraced - set(m)
    assert m["serve_tok_s"] > 0 and m["live_seqs_mean"] > 1
    # no layer caches a key: a token costs the pool nothing, and printed so
    assert m["kv_bytes_per_token.tok"] == 0.0
    dim = state_dim(32)
    assert m["state_bytes_per_seq"] == L * 2 * (32 + 1) * dim * 4
    assert 0 < m["launch_ahead_pct"] <= 100
    eng = obs["engine"]
    stats = kv_pool_stats(eng.kv, eng.allocator)
    assert (stats["pool_bytes"], stats["occupancy"],
            stats["blocks_free"]) == (0, 0.0, eng.allocator.num_blocks)
    assert eng.state_stats()["slots_live"] == 0


def test_the_records_carry_the_retention_counts(tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    launched = [d for d in spans.round_records(obs) if d["program"]]
    assert len(launched) > 10
    for d in launched:
        assert d["ret_rows"] == d["tokens"]
        assert d["ret_pieces"] % L == 0 and d["ret_first"] % L == 0
        assert d["ret_pieces"] >= L * d["n_seqs"]
        assert (d["attn_pairs"], d["dec_ctx_tokens"], d["kv_blocks"],
                d["atoms"]) == (0, 0, 0, 0)
        assert "ssm_rows" not in d
    assert any(d["ret_first"] for d in launched)
    assert any(d["program"] == "decode_forward"
               and d["ret_pieces"] == L * d["decode_rows"] for d in launched)


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, program, live=16, scan_s=0.014, proj_s=0.004,
               gate_s=0.0002, mlp_s=0.008, tokens=None, pieces=None,
               first=0, scopes=True, chunk_s=0.0):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each launching one ``program`` over ``live`` sequences
    (``tokens`` rows: all but one sequence a one-token row, the last a chunk
    of the rest); on the device the state step's kernel (under ``ret_scan``
    in the compiled text), the projections' and the gate's fusions, the
    MLP's (under no scope) and, where ``chunk_s``, a piece's fusion (under
    ``ret_chunk`` inside ``ret_scan``)."""
    from benchmark import spans

    cfg = spec.Bench().config(CONFIG)
    tokens = live if tokens is None else tokens
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.050, 0.061, 0.072, 0.083, 0.094):
        rounds.append((t, t + took, live, 0))
        t += took + 0.001
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        stages.append({"name": "serve/stage", "data": {
            "stage": "round", "round": i, "t0": t0 + 1e-4, "t1": t1 - 1e-4,
            "launch_t": t0 + 0.0031, "tokens": tokens, "program": program,
            "n_seqs": live, "ret_rows": tokens,
            "decode_rows": live if tokens == live else live - 1,
            "ret_pieces": 8 * live if pieces is None else pieces,
            "ret_first": first}})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", at + 0.004, 0.045])
            start = at + 0.005
            for text, took in (
                    ("%fusion.3 = bf16[16,7168]{1,0} fusion(%x)", proj_s),
                    ("%fusion.4 = f32[16,8]{1,0} fusion(%x)", gate_s),
                    (CALL.format(name="ret_state_step"), scan_s),
                    ("%fusion.6 = bf16[16,17408]{1,0} fusion(%x)", mlp_s),
                    ("%fusion.5 = f32[256,8,5,128]{3,2,1,0} fusion(%x)",
                     chunk_s)):
                if took:
                    ops.append([text, start, took])
                    start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = f'op_name="jit({program})/while/body/'
            return (f'  %fusion.3 = bf16[16,7168]{{1,0}} fusion(%x), '
                    f'metadata={{{path}ret_proj/dot_general"}}\n'
                    f'  %fusion.4 = f32[16,8]{{1,0}} fusion(%x), '
                    f'metadata={{{path}ret_gate/dot_general"}}\n'
                    f'  %ret_state_step.1 = f32[8,4]{{1,0}} custom-call(%a), '
                    f'metadata={{{path}ret_scan/pallas_call"}}\n'
                    f'  %fusion.6 = bf16[16,17408]{{1,0}} fusion(%x), '
                    f'metadata={{{path}dot_general"}}\n'
                    f'  %fusion.5 = f32[256,8,5,128]{{3,2,1,0}} fusion(%x), '
                    f'metadata={{{path}while/body/ret_scan/ret_chunk/dot'
                    f'_general"}}\n')

    per_slot = 8 * 8 * (128 + 1) * 8320 * 4
    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()},
        kv=types.SimpleNamespace(),
        state_stats=lambda: {"bytes_per_slot": per_slot, "slots": 16,
                             "slots_live": live, "dtype": "float32",
                             "layers": 8})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_the_state_readers_on_a_decode_step_with_every_slot_live(family):
    """16 live rows through 8 layers: 128 pieces of 34.3 MB read and written
    = 8.79 GB, 10.7 ms at 819 GB/s, against 14 ms under ``ret_scan``:
    76.7 %. At the floor itself the share reads 100 and cannot pass it."""
    bench = spec.Bench()
    obs = traced_obs(family, "decode_forward")
    piece = 8 * (128 + 1) * 8320 * 4
    ideal = 2 * 8 * 16 * piece / 819e9
    assert ideal == pytest.approx(10.73e-3, rel=2e-3)
    got = bench.reader("state_decode_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.014, rel=1e-6)
    at_floor = traced_obs(family, "decode_forward", scan_s=ideal)
    assert bench.reader("state_decode_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # a decode step has no chunk: the chunk reader reads nothing there
    assert bench.reader("state_chunk_roofline")(obs) is None
    busy = 0.004 + 0.0002 + 0.014 + 0.008
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.0182 / busy, rel=1e-6)
    assert bench.reader("state_bytes_per_seq")(obs) == 8 * piece


def test_the_chunk_reader_on_a_mixed_round(family):
    """One 753-row chunk (3 pieces of 251, the first its sequence's first)
    beside 15 one-token rows, 8 layers. The reader takes the PIECES alone,
    at the least work: the quadratic part over the causal half, ``phi(Q) S``
    for the two pieces with a predecessor, the update for all three, the
    state at its 8,256 features read twice and written three times a layer;
    the 15 rows' state step (12 ms here, under ``ret_scan`` alone) is
    neither in its time nor in its work."""
    bench = spec.Bench()
    obs = traced_obs(family, "ragged_forward", tokens=768,
                     pieces=8 * (15 + 3), first=8, scan_s=0.012,
                     chunk_s=0.010)
    a = family.arch(obs["config"])
    f = 128 * 129 // 2
    assert family.features(a) == f == 8256
    quad = 4 * 128 * 40 * (251 * 252 / 2)
    read = 2 * 251 * 40 * f * 129
    write = 2 * 251 * 8 * f * 129
    assert family.retention_chunk_flops(a, 251, False) == quad + read + write
    fl = 8 * (3 * (quad + write) + 2 * read)
    state = 8 * f * 129 * 4
    by = 8 * (753 * family.retention_row_bytes(a) + 5 * state)
    ideal = max(fl / 197e12, by / 819e9)
    assert ideal == fl / 197e12         # the pieces are compute-bound
    got = bench.reader("state_chunk_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.010, rel=1e-6)
    assert 10 < got < 100
    at_floor = traced_obs(family, "ragged_forward", tokens=768,
                          pieces=8 * 18, first=8, scan_s=0.012,
                          chunk_s=ideal)
    assert bench.reader("state_chunk_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # a mixed round without the inner scope: nothing
    assert bench.reader("state_chunk_roofline")(traced_obs(
        family, "ragged_forward", tokens=768, pieces=8 * 18)) is None
    # the layers' share counts both, the piece under its inner scope too
    busy = 0.004 + 0.0002 + 0.012 + 0.008 + 0.010
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.0262 / busy, rel=1e-6)
    assert bench.reader("state_decode_roofline")(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(tiny_cell, family,
                                                           name):
    """No trace (the CPU); a program without the scopes, the counters or
    ``state_stats()`` (the parent): ``None``, not 0, and nothing raised."""
    bench = spec.Bench()
    obs, m = tiny_cell
    assert name not in m and bench.reader(name)(obs) is None
    assert bench.reader(name)({**obs, "stages": [], "engine": None}) is None
    parent = traced_obs(family, "decode_forward", scopes=False)
    for s in parent["stages"]:
        for field in ("ret_rows", "ret_pieces", "ret_first", "decode_rows"):
            del s["data"][field]
    parent["engine"] = types.SimpleNamespace(
        compiled_programs=parent["engine"].compiled_programs,
        kv=types.SimpleNamespace())
    assert bench.reader(name)(parent) is None
    # a Mamba model's engine (state_stats without ``layers``) and another
    # family on a traced run: nothing of the retention to read either
    other = spec.Bench().family({"model_type": "nemotron_h"})
    mamba = traced_obs(family, "decode_forward", scopes=False)
    mamba["engine"].state_stats = lambda: {"bytes_per_slot": 1, "slots": 4}
    assert bench.reader(name)({**mamba, "family": other}) is None
