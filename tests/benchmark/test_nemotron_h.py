"""The ``nemotron_h`` configuration, its cell and its readers: the file
against the catalog row and the program's preset; a tiny cell of the family
driven on the CPU through ``tiny.drive`` (once roomy, once with a pool so
tight that streams are evicted and prefilled again); the new readers on
hand-made observations."""
import json
import types

import numpy as np
import pytest

from benchmark import spec

from ..test_nemotron_h import ENGINE, HF, overrides

CELL, CONFIG, MIX = ("nemo3-reason-sat", "nemotron3-nano-ep4-d26",
                     "reason-short-sat")
REDUCED = ["hybrid_override_pattern", "n_routed_experts",
           "num_hidden_layers", "vocab_size"]
NEW = ["moe_relu2_roofline", "state_share_pct", "state_decode_roofline",
       "state_chunk_roofline", "state_bytes_per_seq"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


# ------------------------------------------------- the file and the preset
def test_the_configuration_departs_from_the_source_only_where_it_says():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == REDUCED
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["run"]) == (value, cfg[key])
        else:
            assert cfg[key] == value, key
    assert cfg["layer_shared_by"] == 4
    assert cfg["hybrid_override_pattern"] == \
        row["config"]["hybrid_override_pattern"][:26]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"] * 4,
            cfg["vocab_size"] * 4) == (26, 128, 131072)


def test_the_preset_has_the_published_widths(family):
    """The catalog's ``config``, uncut, is what the program's preset says;
    the file's cut is what its overrides make of it."""
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    whole = get_config("nemotron-3-nano")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    assert (whole.pattern_count("M"), whole.pattern_count("E"),
            whole.pattern_count("*")) == (23, 23, 6)
    cfg = spec.Bench().config(CONFIG)
    cut = build_model(cfg["preset"], **cfg["overrides"]).config
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    # my arithmetic of ISSUE 37, bf16: 8.9 GB of weights held here
    assert cut.expert_width_stored == 1920
    assert 4.3e9 < cut.param_count() < 4.6e9


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert bench._entry("configs", CONFIG)["reduced"] == REDUCED
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert reports >= {
        "start_to_chip_s", "live_seqs_mean", "ragged_tile_fill_pct",
        "moe_share_pct", "expert_load_max_over_mean", "itl_p99_ms.moe",
        "round_p50_ms.moe", "share_ragged_rounds_pct.moe",
        "serve_program_gib.moe", "decode_fwd_ms.moe", "ragged_fwd_ms.moe",
        "serve_idle_pct.moe", "launch_ahead_pct",
        "moe_tile_fill_pct", "kv_bytes_per_token.tok", *NEW}
    assert "moe_roofline" not in reports     # three matrices an expert
    for m in bench.metrics_of(CELL, "per_layer"):
        assert m["moves"] in ("serve_tok_s", "setup_s")
    # the cell joins the accepted entries of the readers it shares (the
    # plain name where that moves serve_tok_s, ``.tok`` where it does not)
    assert bench.resolved("launch_ahead_pct") == ("launch_ahead_pct", {})
    assert bench.resolved("moe_tile_fill_pct") == ("moe_tile_fill_pct", {})
    assert json.loads(bench._find(
        "metrics", "kv_bytes_per_token.tok", (".json",)).read_text()) == {
            "reader": "kv_bytes_per_token"}


def test_the_mix_is_the_issues_grid_and_fits_the_pools():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    prompts = sorted(p for p, _ in pairs)
    assert (mix["kind"], mix["clients"], len(pairs)) == ("closed", 128, 1024)
    assert mix["prompt_len"] == {"dist": "lognormal", "min": 32, "max": 2048,
                                 "median": 256, "sigma": 0.8}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert 32 <= prompts[0] < 40 and 1900 < prompts[-1] <= 2048
    assert sum(o for _, o in pairs) / 1024 == pytest.approx(512, abs=2)
    assert mix["clients"] == cfg["max_sequences"]
    worst = sorted((p + o for p, o in pairs), reverse=True)[:128]
    assert worst[0] <= cfg["max_context"]
    assert sum(-(-t // cfg["block_size"]) for t in worst) < cfg["num_blocks"]


# ------------------------------------------------------------ the tiny cell
def _tiny_bench(root, family, name, engine, policy):
    from . import tiny

    bench = tiny.make_root(root)
    doc = bench.doc
    cfg = {**HF, "source": "tests", "path": "serve",
           "preset": "nemotron-3-nano",
           "overrides": {k: v for k, v in overrides(family).items()
                         if k != "dtype"},
           "dtype": "float32", "engine": {**ENGINE, **engine},
           "policy": policy}
    (root / "extra" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": name, "source": "tests", "reduced": [],
                           "why": "tiny",
                           "file": f"extra/configs/{name}.json"})
    doc["workloads"].append({"name": f"{name}-cell", "chips": 1,
                             "config": name, "why": "tiny",
                             "traffic": "tiny-closed"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(f"{name}-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, f"{name}-cell", seed=2**31 + 37)


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, family):
    return _tiny_bench(tmp_path_factory.mktemp("bench"), family, "tiny-nemo",
                       {"max_tokens_per_batch": 32, "block_size": 16,
                        "num_blocks": 32}, {"admission": "none"})


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    # everything the cell lists that needs no device trace (the CPU has
    # none) and no atoms (the xla attention takes none)
    bench = spec.Bench()
    untraced = {x["name"] for x in bench.metrics_of(CELL, "per_layer")
                if x["source"] != "device_trace"} - {
        "start_to_chip_s", "ragged_tile_fill_pct"}
    assert untraced <= set(m), untraced - set(m)
    assert m["serve_tok_s"] > 0 and m["live_seqs_mean"] > 1
    eng = obs["engine"]
    # two Mamba layers of [4 heads, 8, 16] float32 + [3, 96] float32
    assert m["state_bytes_per_seq"] == 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert eng.state_stats()["slots_live"] == 0
    # ONE attention layer's K and V, 2 heads of 8, float32
    assert m["kv_bytes_per_token.tok"] == 2 * 2 * 8 * 4
    stats = eng.moe_stats()
    assert stats["load"].shape == (2, 8)
    assert (stats["load"].sum(1) == 3 * stats["live_tokens"]).all()
    assert m["expert_load_max_over_mean"] >= 1
    assert 0 < m["moe_tile_fill_pct"] <= 100
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_the_records_carry_the_rows_and_pieces_of_the_mamba_layers(
        tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    launched = [d for d in spans.round_records(obs) if d["program"]]
    assert len(launched) > 10
    for d in launched:
        assert d["ssm_rows"] == d["tokens"]
        if d["program"] == "decode_forward":
            assert d["ssm_pieces"] == 2 * d["n_seqs"]
        else:      # a piece every 8 rows of a chunk, in each of 2 layers
            assert 2 * d["n_seqs"] <= d["ssm_pieces"] \
                <= 2 * (d["n_seqs"] + d["tokens"] // 8)


def test_a_tight_pool_evicts_and_the_run_is_still_correct(tmp_path, family):
    """``preempt_policy: requeue`` under a pool of 6 blocks: evicted streams
    are prefilled again from a zero state and the run's reference check,
    then held against the WHOLE output of such a stream, passes."""
    obs, _m = _tiny_bench(tmp_path, family, "tiny-nemo-tight",
                          {"num_blocks": 6, "block_size": 8,
                           "max_context": 48},
                          {"admission": "none", "preempt_policy": "requeue"})
    again = [r for r in obs["requests"] if r["evictions"]]
    assert again and all(r["closed"] == "done" for r in again)
    assert obs["correct"] and obs["failed"] == 0


# --------------------------------------- the new readers, hand-made traces
CALL = ('%{name}.1 = f32[8,4]{{1,0}} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


def traced_obs(family, program, live=128, scan_s=0.010, conv_s=0.001,
               gemm_s=0.012, proj_s=0.003, pieces=None, tokens=None,
               scopes=True, chunk_s=0.0):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each launching one ``program`` over ``live`` sequences
    (``tokens`` rows: all but one sequence a one-token row, the last a
    chunk of the rest); on the device the state step's kernel (under
    ``ssm_scan`` in the compiled text), the convolution's fusion, a
    projection, the grouped GEMMs and, where ``chunk_s``, a piece's fusion
    (under ``ssm_chunk`` inside ``ssm_scan``)."""
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
            "n_seqs": live, "ssm_rows": tokens,
            "decode_rows": live if tokens == live else live - 1,
            "ssm_pieces": 12 * live if pieces is None else pieces,
            "moe_touched": 11 * 32, "moe_rows": tokens * 6 * 11 // 4}})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", at + 0.004, 0.045])
            # one after another, as a chip runs them
            start = at + 0.005
            for text, took in (
                    ("%fusion.3 = bf16[128,10304]{1,0} fusion(%x)", proj_s),
                    ("%fusion.4 = f32[128,6144]{1,0} fusion(%x)", conv_s),
                    (CALL.format(name="ssm_state_step"), scan_s),
                    (CALL.format(name="grouped_act"), gemm_s),
                    ("%fusion.5 = f32[128,8,512]{2,1,0} fusion(%x)",
                     chunk_s)):
                if took:
                    ops.append([text, start, took])
                    start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = f'op_name="jit({program})/while/body/'
            return (f'  %fusion.3 = bf16[128,10304]{{1,0}} fusion(%x), '
                    f'metadata={{{path}ssm_proj/dot_general"}}\n'
                    f'  %fusion.4 = f32[128,6144]{{1,0}} fusion(%x), '
                    f'metadata={{{path}ssm_conv/mul"}}\n'
                    f'  %ssm_state_step.1 = f32[8,4]{{1,0}} custom-call(%a), '
                    f'metadata={{{path}ssm_scan/pallas_call"}}\n'
                    f'  %fusion.5 = f32[128,8,512]{{2,1,0}} fusion(%x), '
                    f'metadata={{{path}while/body/ssm_scan/ssm_chunk/dot'
                    f'_general"}}\n'
                    f'  %grouped_act.1 = f32[8,4]{{1,0}} custom-call(%a), '
                    f'metadata={{{path}moe_experts/pallas_call"}}\n')

    ssm = types.SimpleNamespace(shape=(12, 129, 8, 128, 512),
                                dtype=np.dtype("float32"))
    per_slot = 12 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()},
        kv=types.SimpleNamespace(ssm=ssm),
        state_stats=lambda: {"bytes_per_slot": per_slot, "slots": 128,
                             "slots_live": live, "dtype": "float32"})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_the_state_readers_on_a_decode_step_with_every_slot_live(family):
    """128 live rows through 12 Mamba layers: 1,536 pieces of 2 MiB + 36 KiB
    read and written = 6.55 GB, 8.0 ms at 819 GB/s, against 11 ms under the
    two scopes: 72.7 %. At the floor itself (the time = the bytes' time) the
    share reads 100 and cannot pass it."""
    bench = spec.Bench()
    obs = traced_obs(family, "decode_forward")
    piece = 64 * 64 * 128 * 4 + 3 * 6144 * 2
    ideal = 2 * 12 * 128 * piece / 819e9
    assert ideal == pytest.approx(8.0e-3, rel=0.01)
    got = bench.reader("state_decode_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.011, rel=1e-6)
    at_floor = traced_obs(family, "decode_forward", scan_s=ideal, conv_s=0.0)
    assert bench.reader("state_decode_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # a decode step has no chunk: the chunk reader reads nothing there
    assert bench.reader("state_chunk_roofline")(obs) is None
    busy = 0.003 + 0.001 + 0.010 + 0.012
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.014 / busy, rel=1e-6)
    assert bench.reader("state_bytes_per_seq")(obs) == 12 * piece
    # two matrices an expert at the published 1856: 352 expert-layers read
    moe = bench.reader("moe_relu2_roofline")(obs)
    fl, by = family.expert_work(family.arch(obs["config"]), 352,
                                128 * 6 * 11 // 4)
    assert by == (352 * 2 * 2688 * 1856 + 2 * 2112 * 2688) * 2
    assert moe == pytest.approx(100 * (by / 819e9) / 0.012, rel=1e-6)
    assert 50 < moe < 100
    three = bench._module("metrics", "moe_roofline").expert_work(
        family.arch(obs["config"]), 352, 2112)
    assert three[1] > 1.45 * by          # what moe_roofline would count


def test_the_chunk_reader_on_a_mixed_round(family):
    """One 641-row chunk (6 pieces) beside 127 one-token rows, 12 layers.
    The reader takes the PIECES alone: 641 rows' recurrence (6 x 64 x 64 x
    128 FLOPs a row and layer), those rows in and out and 6 x 12 pieces of
    2 MiB of SSM state read and written, against the time under
    ``ssm_chunk``; the 127 rows' state step (20 ms here, under ``ssm_scan``
    alone) is neither in its time nor in its bytes."""
    bench = spec.Bench()
    obs = traced_obs(family, "ragged_forward", tokens=768,
                     pieces=12 * (127 + 6), scan_s=0.020, chunk_s=0.004)
    arch = family.arch(obs["config"])
    state = 64 * 64 * 128 * 4
    assert family.ssm_scan_flops(arch) == 6 * 64 * 64 * 128
    assert family.ssm_row_bytes(arch) == (2 * 4096 + 2 * 1024) * 2 + 64 * 4
    assert family.ssm_state_bytes(arch) == state + 3 * 6144 * 2
    fl = 641 * 12 * 6 * 64 * 64 * 128
    by = 641 * 12 * family.ssm_row_bytes(arch) + 2 * 12 * 6 * state
    ideal = max(fl / 197e12, by / 819e9)
    assert ideal == by / 819e9
    got = bench.reader("state_chunk_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.004, rel=1e-6)
    assert 10 < got < 100
    # at the floor itself it reads 100, whatever the one-token rows cost
    at_floor = traced_obs(family, "ragged_forward", tokens=768,
                          pieces=12 * (127 + 6), scan_s=0.020,
                          chunk_s=ideal)
    assert bench.reader("state_chunk_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # a mixed round without the scope (this PR's first program): nothing
    assert bench.reader("state_chunk_roofline")(traced_obs(
        family, "ragged_forward", tokens=768, pieces=12 * 133)) is None
    # the mixers' share counts both, the piece under its inner scope too
    busy = 0.003 + 0.001 + 0.020 + 0.012 + 0.004
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.028 / busy, rel=1e-6)
    assert bench.reader("state_decode_roofline")(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(tiny_cell, family,
                                                           name):
    """No trace (the CPU); a program without the scopes, the counters or
    ``state_stats()`` (the parent): ``None``, not 0, and nothing raised."""
    bench = spec.Bench()
    obs, m = tiny_cell
    if name != "state_bytes_per_seq":
        assert name not in m and bench.reader(name)(obs) is None
    assert bench.reader(name)({**obs, "stages": [], "engine": None}) is None
    parent = traced_obs(family, "decode_forward", scopes=False)
    for s in parent["stages"]:
        for field in ("ssm_rows", "ssm_pieces", "moe_touched", "moe_rows",
                      "decode_rows"):
            del s["data"][field]
    parent["engine"] = types.SimpleNamespace(
        compiled_programs=parent["engine"].compiled_programs,
        kv=types.SimpleNamespace())
    assert bench.reader(name)(parent) is None
    # another family on a traced run: nothing of the state to read either
    other = spec.Bench().family({"model_type": "olmoe"})
    if name != "moe_relu2_roofline":
        assert bench.reader(name)({**parent, "family": other}) is None
