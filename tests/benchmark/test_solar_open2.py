"""The ``solar_open2`` configuration, its cell and its readers: the file
against the catalog row and the program's preset; the cut's arithmetic; the
family's counts against a hand count; the mix; the five new readers on
hand-made traces and on nothing."""
import json
import types

import pytest

from benchmark import spec

CELL, CONFIG, MIX = "solar2-agent-sat", "solar-open2-ep8-d4", "agent-steps-sat"
NEW = ["state_share_pct", "state_decode_roofline", "state_chunk_roofline",
       "kda_piece_rows_mean", "gqa_attn_share_pct"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["gqa_layers", "n_routed_experts", "num_hidden_layers",
           "vocab_size"]
L = 3               # delta-rule layers of the cut
STATE = 64 * 128 * 128 * 4          # a sequence's state in one layer: 4 MiB
SLOT_LAYER = STATE + 3 * 24576 * 2  # ... and the convolution's tail
CALL = ('%{name}.1 = f32[8,4]{{1,0}} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family({"model_type": "solar_open2"})


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "Solar-Open2-250B")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_with_every_width_unchanged():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == REDUCED and cfg["layer_shared_by"] == 8
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    for key, (published, run, counts) in {
            "num_hidden_layers": (48, 4, "layers"),
            "gqa_layers": (list(range(0, 48, 4)), [0], "layers"),
            "n_routed_experts": (320, 40, "experts"),
            "vocab_size": (196608, 24576, "vocabulary")}.items():
        cut = cfg["reduced"][key]
        assert (cut["published"], cut["run"], cut["counts"]) \
            == (published, run, counts)
        assert cut["published"] == row["config"][key] and cfg[key] == run
    # the widths ISSUE 55 names, as published
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["norm_topk_prob"], cfg["routed_scaling_factor"],
            cfg["use_rope"]) == (4096, 1280, 8, 1, True, 1, False)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (64, 8, 128)
    assert set(cfg["assumed"]) >= {
        "kda", "gqa_gate", "state", "router", "experts", "experts_here",
        "weights", "dtype", "kv_pool", "prefix_cache"}
    assert cfg["engine"] == {
        "max_context": 9216, "max_sequences": 256, "num_blocks": 8192,
        "block_size": 64, "max_tokens_per_batch": 768,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == {"admission": "none",
                             "preempt_policy": "requeue"}
    assert (cfg["path"], cfg["dtype"], cfg["preset"]) \
        == ("serve", "bfloat16", "solar-open2")
    assert cfg["overrides"] == {"num_layers": 8, "layer_pattern": "*EKEKEKE",
                                "num_experts_held": 40, "vocab_size": 24576}
    assert "twelve pipeline stages" in cfg["deployment"]


def test_the_preset_has_the_published_widths_and_the_cut_its_arithmetic(
        family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("solar-open2")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cfg = spec.Bench().config(CONFIG)
    cut = get_config("solar-open2", **cfg["overrides"])
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    assert (cut.num_kv_layers, cut.state_layers, cut.num_moe_layers,
            cut.experts_held, cut.num_experts) == (1, 3, 4, 40, 320)
    # ISSUE 55's arithmetic: 3,308 M parameters = 6.16 GiB resident in bf16
    assert cut.param_count() / 1e6 == pytest.approx(3308, abs=2)
    assert cut.param_count() * 2 / 2**30 == pytest.approx(6.16, abs=0.01)
    # the state: 4 MiB + a 144 KiB tail a layer, 12.4 MiB a sequence, 3.12
    # GiB for 257 slots; one array under 2^31 elements
    a = family.arch(cfg)
    assert family.kda_state_bytes(a) == STATE == 4 * 2**20
    assert 3 * 24576 * 2 == 144 * 1024
    assert L * SLOT_LAYER / 2**20 == pytest.approx(12.42, abs=0.01)
    slots = cfg["engine"]["max_sequences"] + 1
    assert slots * L * SLOT_LAYER / 2**30 == pytest.approx(3.12, abs=0.01)
    assert L * slots * 64 * 128 * 128 < 2**31
    # a cached token: one layer, 8 heads of 128, K and V in bf16
    assert 8 * 128 * 2 * 2 == 4096
    kv = cfg["engine"]["num_blocks"] * 64 * 4096
    assert kv / 2**30 == 2.0
    total = cut.param_count() * 2 + slots * L * SLOT_LAYER + kv
    assert 11.0 < total / 2**30 < 11.5


def test_the_familys_counts_against_a_hand_count(family):
    hf = {"model_type": "solar_open2", "hidden_size": 64,
          "num_hidden_layers": 4, "gqa_layers": [0],
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "vocab_size": 512, "intermediate_size": 160,
          "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                                 "num_heads": 2, "num_kv_heads": None},
          "moe_intermediate_size": 24, "rms_norm_eps": 1e-5,
          "use_gqa_gate": True, "kda_allow_neg_eigval": True,
          "n_routed_experts": 8, "n_shared_experts": 1,
          "num_experts_per_tok": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 1}
    a = family.arch(hf)
    assert family.layer_pattern(hf) == "*EKEKEKE"
    assert family.layer_counts(a) == (1, 3)
    assert family.kda_step_flops(a) == 7 * 2 * 8 * 8
    assert family.kda_state_bytes(a) == 2 * 8 * 8 * 4
    assert family.kda_row_bytes(a) == (5 * 16 + 2) * 4
    kda_w = 4 * 64 * 16 + 2 * (64 * 8 + 8 * 16) + 64 * 2
    attn_w = 64 * 16 * (2 * 4 + 2 * 2) + 64 * 64
    moe_w = 64 * 8 + 3 * 64 * (24 * 2 + 24)
    params = 3 * kda_w + attn_w + 4 * moe_w + 64 * 512
    assert family.matmul_params(a) == params
    assert family.train_flops_per_token(a, 8) == 6 * params \
        + 3 * 4 * 16 * 4 * (8 * 9 // 2) / 8 + 3 * 7 * 2 * 64 * 3
    # at the cell's widths: ISSUE 55's 64 heads x 7 x 128 x 128
    cell = family.arch(spec.Bench().config(CONFIG))
    assert family.kda_step_flops(cell) == 64 * 7 * 128 * 128
    assert (cell["num_experts"], cell["experts_held"], cell["num_layers"]) \
        == (320, 40, 4)


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == REDUCED
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert reports >= {"start_to_chip_s", *NEW}
    for m in bench.doc["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert len(bench.doc["per_layer"]) <= 128


def test_the_mix_is_the_issues_deck():
    """ISSUE 55's parameters, all of them: 256 callers, a shuffled deck of
    2,048 pairs, its two distributions; nothing in the file follows the
    program's rate (``spread`` says what six seeds read on it)."""
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], len(pairs)) == ("closed", 256, 2048)
    assert mix.get("order", "shuffle") == "shuffle" and "2.80 %" in mix["spread"]
    assert mix["prompt_len"] == {"dist": "lognormal", "min": 64, "max": 8192,
                                 "median": 512, "sigma": 0.9}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["clients"] == cfg["max_sequences"]
    assert max(p + o for p, o in pairs) <= cfg["max_context"]
    assert 700 < sum(p for p, _ in pairs) / 2048 < 800
    assert sum(o for _, o in pairs) / 2048 == 640
    # a cycle is the whole deck in a fresh order, whoever asks
    plan = traffic.ClosedPlan(mix, 7, 1000)
    dealt = sorted((len(r["tokens"]), r["max_new_tokens"])
                   for r in (plan.take(c % 256) for c in range(2048)))
    assert dealt == sorted(pairs)
    # the pool holds the deck's mean 256 times over with a block's rounding
    # a caller (the issue's ~280 k in flight of 524 k); the longest 256
    # pairings at once it does not, and `requeue` answers for that draw
    mean = sum(p + o / 2 for p, o in pairs) / 2048
    assert 256 * (mean + 64) < 0.6 * cfg["num_blocks"] * 64
    # parity's three probes: a prompt of one piece, several pieces with a
    # ragged last one, and a 7.7 k prompt (121 pieces) with a decode tail
    # of 256 and more
    probes = sorted(pairs)
    short, mid, long = probes[0], probes[len(probes) // 2], probes[-1]
    assert short == (64, 256) and mid[0] % 64 and mid[0] > 4 * 64
    assert long[0] > 7 * 1024 and long[1] >= 256


def test_the_tool_that_reads_what_the_cell_does_not_list():
    """``tools/bench_unlisted.py`` (PERF.md section 5's row of this cell):
    the accepted entries it names join the cell's list for its own run, each
    with a reader, and ``BENCHMARK.json``'s own lists are as they were."""
    import importlib.util

    path = spec.ROOT / "tools" / "bench_unlisted.py"
    sp = importlib.util.spec_from_file_location("bench_unlisted", path)
    tool = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(tool)
    bench = spec.Bench()
    listed = [m["name"] for m in bench.metrics_of(CELL, "per_layer")]
    metrics_of, reader = tool.unlisted(set(tool.READERS), tool.SCOPES)
    names = [m["name"] for m in metrics_of(bench, CELL, "per_layer")]
    assert names[:len(listed)] == listed and names[-1] == "_breakdown"
    assert set(names[len(listed):-1]) == set(tool.READERS) - set(listed)
    assert len(set(names)) == len(names)
    assert all(callable(reader(bench, n)) for n in names)
    assert metrics_of(bench, CELL, "end_to_end") \
        == bench.metrics_of(CELL, "end_to_end")
    assert [m["name"] for m in bench.metrics_of(CELL, "per_layer")] == listed


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, program, live=256, step_s=0.0033, conv_s=0.0004,
               proj_s=0.004, gate_s=0.001, mlp_s=0.010, attn_s=0.0008,
               attn_gate_s=0.0002, tokens=None, pieces=None, first=0,
               scopes=True, chunk_s=0.0):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each launching one ``program`` over ``live`` sequences
    (``tokens`` rows: all but one sequence a one-token row, the last a chunk
    of the rest); on the device the state step's kernel and the paged
    decode kernel, the projections', the convolution's, the gates' and the
    experts' fusions (the experts' under no scope of these readers) and,
    where ``chunk_s``, a piece's fusion (under ``kda_chunk`` inside
    ``kda_scan``)."""
    from benchmark import spans

    cfg = spec.Bench().config(CONFIG)
    tokens = live if tokens is None else tokens
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.050, 0.061, 0.072, 0.083, 0.094):
        rounds.append((t, t + took, live, 0))
        t += took + 0.001
    ops_of = (("%fusion.3 = bf16[256,24576]{1,0} fusion(%x)", proj_s),
              ("%fusion.4 = f32[256,24576]{1,0} fusion(%x)", conv_s),
              ("%fusion.7 = f32[256,64,128]{2,1,0} fusion(%x)", gate_s),
              (CALL.format(name="kda_state_step"), step_s),
              (CALL.format(name="paged_decode"), attn_s),
              ("%fusion.8 = bf16[256,8192]{1,0} fusion(%x)", attn_gate_s),
              ("%fusion.6 = bf16[2048,1280]{1,0} fusion(%x)", mlp_s),
              ("%fusion.5 = f32[64,64,128]{2,1,0} fusion(%x)", chunk_s))
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        stages.append({"name": "serve/stage", "data": {
            "stage": "round", "round": i, "t0": t0 + 1e-4, "t1": t1 - 1e-4,
            "launch_t": t0 + 0.0031, "tokens": tokens, "program": program,
            "n_seqs": live, "kda_rows": tokens,
            "decode_rows": live if tokens == live else live - 1,
            "kda_pieces": L * live if pieces is None else pieces,
            "kda_first": first}})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", at + 0.004, 0.045])
            start = at + 0.005
            for text, took in ops_of:
                if took:
                    ops.append([text, start, took])
                    start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = f'op_name="jit({program})/while/body/'
            lines = (("fusion.3", "kda_proj/dot_general"),
                     ("fusion.4", "kda_conv/mul"),
                     ("fusion.7", "kda_gate/mul"),
                     ("kda_state_step.1", "kda_scan/kda_step/pallas_call"),
                     ("paged_decode.1", "pallas_call"),
                     ("fusion.8", "attn_gate/dot_general"),
                     ("fusion.6", "moe_experts/dot_general"),
                     ("fusion.5", "while/body/kda_scan/kda_chunk/dot_general"))
            return "".join(
                f'  %{name} = f32[8,4]{{1,0}} fusion(%x), '
                f'metadata={{{path}{scope}"}}\n' for name, scope in lines)

    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()},
        kv=types.SimpleNamespace(),
        state_stats=lambda: {"bytes_per_slot": L * SLOT_LAYER, "slots": 256,
                             "slots_live": live, "dtype": "float32",
                             "layers": L})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "window": (rounds[0][0], rounds[-1][1]),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_the_state_readers_on_a_decode_step_with_every_slot_live(family):
    """256 live rows through 3 layers: 768 slot-layers of 4 MiB + 144 KiB
    read and written = 6.67 GB, 8.15 ms at 819 GB/s, against 3 x 3.7 ms
    under ``kda_conv`` + ``kda_step``: 73 %. At the floor itself the share
    reads 100 and cannot pass it."""
    bench = spec.Bench()
    obs = traced_obs(family, "decode_forward")
    ideal = 2 * L * 256 * SLOT_LAYER / 819e9
    assert ideal == pytest.approx(8.15e-3, rel=2e-3)
    got = bench.reader("state_decode_roofline")(obs)
    assert got == pytest.approx(100 * ideal / (0.0033 + 0.0004), rel=1e-6)
    at_floor = traced_obs(family, "decode_forward", step_s=ideal, conv_s=0.0)
    assert bench.reader("state_decode_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # a decode step has no chunk: the chunk readers read nothing there
    assert bench.reader("state_chunk_roofline")(obs) is None
    assert bench.reader("kda_piece_rows_mean")(obs) is None
    kda = 0.004 + 0.0004 + 0.001 + 0.0033
    busy = kda + 0.0008 + 0.0002 + 0.010
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * kda / busy, rel=1e-6)
    assert bench.reader("gqa_attn_share_pct")(obs) == pytest.approx(
        100 * 0.0010 / busy, rel=1e-6)
    assert bench.reader("state_bytes_per_seq")(obs) == L * SLOT_LAYER


def test_the_chunk_readers_on_a_mixed_round(family):
    """One 300-row chunk (5 pieces: four of 64 and one of 44, the first its
    sequence's first) beside 255 one-token rows, 3 layers. The reader takes
    the PIECES alone, by the sequential form's count: 7 x 64 x 128 x 128
    FLOPs a row and layer, the rows in and out, the state read four times
    and written five a layer; the 255 rows' state step is neither in its
    time nor in its work. Given the roofline's own time it reads 100."""
    bench = spec.Bench()
    kw = dict(tokens=555, pieces=L * (255 + 5), first=L)
    obs = traced_obs(family, "ragged_forward", chunk_s=0.0012, **kw)
    a = family.arch(obs["config"])
    fl = L * 300 * family.kda_step_flops(a)
    by = L * (300 * family.kda_row_bytes(a) + 9 * STATE)
    ideal = max(fl / 197e12, by / 819e9)
    assert ideal == by / 819e9          # the pieces are bound by the state
    got = bench.reader("state_chunk_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.0012, rel=1e-6)
    assert 5 < got < 100
    at_floor = traced_obs(family, "ragged_forward", chunk_s=ideal, **kw)
    assert bench.reader("state_chunk_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    assert bench.reader("kda_piece_rows_mean")(obs) == 300 / 5
    # a mixed round without the inner scope: nothing
    assert bench.reader("state_chunk_roofline")(traced_obs(
        family, "ragged_forward", **kw)) is None
    # the layers' share counts both, the piece under its inner scope too
    kda = 0.004 + 0.0004 + 0.001 + 0.0033 + 0.0012
    busy = kda + 0.0008 + 0.0002 + 0.010
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * kda / busy, rel=1e-6)
    assert bench.reader("state_decode_roofline")(obs) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(family, name):
    """No trace (the CPU); a program without the scopes, the counters or
    ``state_stats()`` (the parent); another kind of state: ``None``, not 0,
    and nothing raised."""
    bench = spec.Bench()
    obs = traced_obs(family, "decode_forward")
    untraced = {**obs, "trace": None, "stages": [], "engine": None}
    assert bench.reader(name)(untraced) is None
    # (the parent has no such kernel either)
    parent = traced_obs(family, "ragged_forward", scopes=False, tokens=555,
                        pieces=L * 260, first=L, chunk_s=0.001, step_s=0.0)
    for s in parent["stages"]:
        for field in ("kda_rows", "kda_pieces", "kda_first"):
            del s["data"][field]
    parent["engine"] = types.SimpleNamespace(
        compiled_programs=parent["engine"].compiled_programs,
        kv=types.SimpleNamespace())
    assert bench.reader(name)(parent) is None
    # a Mamba model's records and scopes on a traced run: nothing of the
    # delta rule to read either
    other = spec.Bench().family({"model_type": "nemotron_h"})
    mamba = traced_obs(family, "ragged_forward", scopes=False, tokens=555,
                       pieces=L * 260, chunk_s=0.001, step_s=0.0)
    for s in mamba["stages"]:
        d = s["data"]
        d["ssm_rows"], d["ssm_pieces"] = d.pop("kda_rows"), d.pop(
            "kda_pieces")
        del d["kda_first"]
    assert bench.reader(name)({**mamba, "family": other}) is None
