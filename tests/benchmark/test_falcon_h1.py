"""The ``falcon_h1`` configuration, its cell and its readers: the file against
the catalog row and the program's preset; what the family says of its kind of
layer; a tiny cell of the family driven on the CPU through ``tiny.drive``;
the reference's products of bf16 matrices as stored; the two new readers on a hand-made
trace."""
import json
import types

import numpy as np
import pytest

from benchmark import spec
from benchmark.reference import layer_kind

from ..test_falcon_h1 import ENGINE, HF, overrides

CELL, CONFIG, MIX = ("falconh1-chat-sat", "falcon-h1-34b-d6",
                     "chat-history-sat")
JOINED = ["live_seqs_mean", "state_share_pct", "state_decode_roofline",
          "state_chunk_roofline", "state_bytes_per_seq", "decode_fwd_ms.moe",
          "ragged_fwd_ms.moe", "round_p50_ms.moe",
          "share_ragged_rounds_pct.moe", "serve_program_gib.moe",
          "serve_idle_pct.moe", "itl_p99_ms.moe", "launch_ahead_pct",
          "ragged_row_fill_pct"]
NEW = ["h1_attn_share_pct"]
# a reader with its file and its tests and NO entry: the accepted tests pin the
# list at 112 (``test_spec.py``), so ``tools/bench_unlisted.py --readers``
# reads it until a ``benchmark`` issue makes room (PERF.md section 7, PR 63)
UNLISTED = ["head_share_pct"]
SETUP = ["start_to_chip_s", "setup_trace_s", "setup_lower_s",
         "setup_compile_s", "setup_cache_miss_programs", "setup_warm_run_s"]


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


# ------------------------------------------------- the file and the preset
def test_the_configuration_departs_from_the_source_only_in_its_depth():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["run"], cut["counts"]) == (72, 6, "layers")
    for key, value in row["config"].items():
        assert cfg[key] == (6 if key == "num_hidden_layers" else value), key
    assert cfg["overrides"] == {"num_layers": 12, "layer_pattern": "HF" * 6}


def test_the_preset_has_the_published_widths(family):
    """The catalog's ``config``, uncut, is what the program's preset says
    (the twelve multipliers of ``mup`` among it); the file's cut is what its
    overrides make of it, and its bytes are ISSUE 63's arithmetic."""
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    whole = get_config("falcon-h1-34b")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    assert (whole.pattern_count("H"), whole.pattern_count("F")) == (72, 72)
    assert whole.mup.key == pytest.approx(128 ** -0.5 / 8)
    assert 33.5e9 < whole.param_count() < 33.8e9
    cfg = spec.Bench().config(CONFIG)
    cut = build_model(cfg["preset"], **cfg["overrides"]).config
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    # a layer 430.12 M, embedding and head 2.674 G: 10.51 GB in bf16
    assert cut.param_count() == 6 * 430_120_032 + 2 * 261_120 * 5_120 + 5_120
    arch = family.arch(cfg)
    assert family.ssm_state_bytes(arch) == (4 << 20) + 3 * 5120 * 2
    assert family.matmul_params(arch) == 6 * (
        5120 * 9248 + 4096 * 5120 + 5120 * 128 * 48
        + 3 * 5120 * 21504) + 5120 * 261_120


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert bench._entry("configs", CONFIG)["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"]: m for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert set(reports) >= {*JOINED, *NEW, *SETUP}
    # CHANGES PR 62: these three miscount a cell like this one
    assert not {"kv_bytes_per_token", "paged_roofline",
                "kv_step_fill_pct"} & set(reports)
    for name in NEW:
        assert reports[name]["workloads"] == [CELL]
        assert reports[name]["moves"] == "serve_tok_s"
    assert not set(UNLISTED) & set(reports)
    assert bench.resolved("h1_attn_share_pct") == (
        "scope_share_pct", {"labels": ["h1_attn"]})
    assert bench.resolved("head_share_pct") == (
        "scope_share_pct", {"labels": ["lm_head"]})


def test_the_mix_is_the_issues_grid_and_fits_the_pools():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    prompts = sorted(p for p, _ in pairs)
    assert (mix["kind"], mix["clients"], len(pairs), mix["order_block"],
            mix["trace_seconds"]) == ("closed", 48, 1024, 64, 5)
    assert mix["prompt_len"] == {"dist": "lognormal", "min": 128,
                                 "max": 4096, "median": 1024, "sigma": 0.8}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert 128 <= prompts[0] < 140 and 4000 < prompts[-1] <= 4096
    assert sum(o for _, o in pairs) / 1024 == pytest.approx(320, abs=2)
    assert mix["clients"] == cfg["max_sequences"]
    # the worst case of ISSUE 63, every caller on 4,096 + 512, fits
    assert cfg["max_context"] == 4096 + 512
    assert 48 * -(-cfg["max_context"] // cfg["block_size"]) \
        <= cfg["num_blocks"]


def test_the_family_says_recurrent_state_with_every_part(family):
    """What ``test_layer_kinds.py`` asks of a family that says the kind (its
    ``SAYS`` table does not know this one: PERF.md section 7), at THIS
    model's bytes: 4 MiB of float32 state a slot and layer."""
    kind = layer_kind(family, "recurrent_state")
    assert set(kind) >= {"share_scopes", "step_scopes", "step_pieces",
                         "chunk_scopes", "chunk_work"}
    assert kind["step_pieces"] == "ssm_pieces"
    assert set(kind["step_scopes"]) <= set(kind["share_scopes"])
    assert layer_kind(family, "selection") is None
    cfg = spec.Bench().config(CONFIG)
    pool = types.SimpleNamespace(shape=(6, 49, 2, 256, 2048),
                                 dtype=np.dtype("float32"))
    per_slot = 6 * ((4 << 20) + 3 * 5120 * 2)
    obs = {"config": cfg, "engine": types.SimpleNamespace(
        kv=types.SimpleNamespace(ssm=pool),
        state_stats=lambda: {"bytes_per_slot": per_slot, "layers": 6})}
    assert kind["slot_layer_bytes"](obs) == (4 << 20) + 3 * 5120 * 2
    work = kind["chunk_work"](obs)
    # a 768-row chunk (6 pieces a layer) beside 47 one-token rows
    record = {"ssm_rows": 768 + 47, "decode_rows": 47,
              "ssm_pieces": 6 * (6 + 47)}
    arch = family.arch(cfg)
    assert work(record) == (
        768 * 6 * 6 * 32 * 128 * 256,
        768 * 6 * family.ssm_row_bytes(arch) + 2 * 36 * (4 << 20))
    assert work({**record, "ssm_rows": 47, "ssm_pieces": 6 * 47}) is None
    assert kind["chunk_work"]({"config": cfg, "engine": None}) is None


def test_the_references_products_of_stored_bf16_are_exact(family,
                                                          monkeypatch):
    """``matmul`` on a bf16 matrix as stored (three bf16 terms of the float32
    rows, no cast of the matrix) against the product in float64, and
    ``Logits`` a block of rows at a time against the product whole."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((7, 64)) * 3.0, jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 30)), jnp.bfloat16)
    want = np.asarray(x, np.float64) @ np.asarray(w.astype(jnp.float32),
                                                   np.float64)
    np.testing.assert_allclose(family.matmul(x, w), want, rtol=0, atol=2e-5)
    # one bf16 term alone is 2^-9 of a row off: a thousand times that
    assert np.abs(np.asarray(jnp.dot(
        x.astype(jnp.bfloat16), w, preferred_element_type=jnp.float32))
        - want).max() > 2e-2
    np.testing.assert_allclose(
        family.matmul(x, w.astype(jnp.float32)), want, atol=2e-5)
    monkeypatch.setattr(family, "ROW_BLOCK", 3)
    logits = family.Logits(x, w, 0.5)
    assert logits.shape == (7, 30) and len(logits) == 7
    np.testing.assert_allclose(np.asarray(logits), 0.5 * want, atol=1e-5)
    np.testing.assert_allclose(logits[2:4], 0.5 * want[2:4], atol=1e-5)


# ------------------------------------------------------------ the tiny cell
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, family):
    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc, name = bench.doc, "tiny-falconh1"
    cfg = {**HF, "source": "tests", "path": "serve",
           "preset": "falcon-h1-34b",
           "overrides": {k: v for k, v in overrides(family).items()
                         if k != "dtype"},
           "dtype": "float32",
           "engine": {**ENGINE, "max_tokens_per_batch": 32, "block_size": 16,
                      "num_blocks": 32},
           "policy": {"admission": "none", "preempt_policy": "requeue"}}
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
    return tiny.drive(bench, f"{name}-cell", seed=2**31 + 63)


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    # everything the cell lists that needs no device trace (the CPU has none)
    bench = spec.Bench()
    untraced = {x["name"] for x in bench.metrics_of(CELL, "per_layer")
                if x["source"] != "device_trace"} - {"start_to_chip_s"}
    assert untraced <= set(m), untraced - set(m)
    assert m["serve_tok_s"] > 0 and m["live_seqs_mean"] > 1
    eng = obs["engine"]
    # three layers of [4 heads, 8, 16] float32 + [3, 96] float32
    assert m["state_bytes_per_seq"] == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    stats = eng.state_stats()
    assert stats["slots_live"] == 0 and stats["layers"] == 3
    assert eng.kv.k.shape[0] == 3        # and a KV row behind each of them
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_the_records_carry_the_rows_and_pieces_of_the_mamba_halves(
        tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    launched = [d for d in spans.round_records(obs) if d["program"]]
    assert len(launched) > 10
    for d in launched:
        assert d["ssm_rows"] == d["tokens"]
        if d["program"] == "decode_forward":
            assert d["ssm_pieces"] == 3 * d["n_seqs"]
        else:      # a piece every 8 rows of a chunk, in each of 3 layers
            assert 3 * d["n_seqs"] <= d["ssm_pieces"] \
                <= 3 * (d["n_seqs"] + d["tokens"] // 8)


# --------------------------------------- the new readers, a hand-made trace
def traced_obs(family, scopes=True):
    """``obs`` of a traced run: three rounds, the middle one traced, a
    ``decode_forward`` whose device operations are a projection and the
    paged kernel under ``h1_attn``, the state step under ``ssm_scan``, the
    feed-forward part under no scope and the head under ``lm_head``."""
    from benchmark import spans

    offset, rounds, t = 5.0, [], 100.0
    for took in (0.020, 0.021, 0.022):
        rounds.append((t, t + took, 48, 0))
        t += took + 0.001
    call = ('%{}.1 = f32[8,4]{{1,0}} custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    ops_of = (("%fusion.1 = bf16[48,2560]{1,0} fusion(%x)", 0.001),
              (call.format("paged_decode"), 0.002),
              (call.format("ssm_state_step"), 0.004),
              ("%fusion.2 = bf16[48,21504]{1,0} fusion(%x)", 0.005),
              ("%fusion.3 = f32[48,261120]{1,0} fusion(%x)", 0.003))
    t0, t1 = rounds[1][:2]
    at = t0 + offset
    host = [[spans.ROUND_SPAN, at, t1 - t0],
            ["PjitFunction(decode_forward)", at + 0.002, 0.001]]
    modules, ops = [["jit_decode_forward(7)", at + 0.004, 0.016]], []
    start = at + 0.0045
    for text, took in ops_of:
        ops.append([text, start, took])
        start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = 'op_name="jit(decode_forward)/while/body/'
            return (f'  %fusion.1 = bf16[48,2560]{{1,0}} fusion(%x), '
                    f'metadata={{{path}h1_attn/dot_general"}}\n'
                    f'  %paged_decode.1 = f32[8,4]{{1,0}} custom-call(%a), '
                    f'metadata={{{path}h1_attn/pallas_call"}}\n'
                    f'  %ssm_state_step.1 = f32[8,4]{{1,0}} custom-call(%a), '
                    f'metadata={{{path}ssm_scan/pallas_call"}}\n'
                    f'  %fusion.2 = bf16[48,21504]{{1,0}} fusion(%x), '
                    f'metadata={{{path}mfu.mlp/dot_general"}}\n'
                    f'  %fusion.3 = f32[48,261120]{{1,0}} fusion(%x), '
                    f'metadata={{op_name="jit(decode_forward)/lm_head/dot'
                    f'_general"}}\n')

    engine = types.SimpleNamespace(
        compiled_programs=lambda: {"decode_forward": Compiled()})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (t0 + offset - 1e-3, t1 + offset + 1e-3),
            "rounds": rounds, "stages": [], "engine": engine,
            "family": family}


def test_the_two_shares_split_the_busy_time_with_the_state_share(family):
    bench = spec.Bench()
    obs = traced_obs(family)
    busy = 0.001 + 0.002 + 0.004 + 0.005 + 0.003
    assert bench.reader("h1_attn_share_pct")(obs) == pytest.approx(
        100 * 0.003 / busy, rel=1e-6)
    assert bench.reader("head_share_pct")(obs) == pytest.approx(
        100 * 0.003 / busy, rel=1e-6)
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.004 / busy, rel=1e-6)


@pytest.mark.parametrize("name", NEW + UNLISTED)
def test_a_new_reader_reads_nothing_where_there_is_nothing(tiny_cell, family,
                                                           name):
    """No trace (the CPU); no engine; a program without the scopes (the
    parent): ``None``, not 0, and nothing raised."""
    bench = spec.Bench()
    obs, m = tiny_cell
    assert name not in m and bench.reader(name)(obs) is None
    assert bench.reader(name)({**obs, "stages": [], "engine": None}) is None
    assert bench.reader(name)(traced_obs(family, scopes=False)) is None
