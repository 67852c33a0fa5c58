"""The ``ouro`` configuration, its cell and its readers: the file against the
catalog row and the program's preset; the family's counts against a hand
count; a tiny cell of the family driven on the CPU through ``tiny.drive``
(once roomy, once with a pool so tight that streams are evicted and
prefilled again); the new readers on hand-made observations."""
import json
import types

import numpy as np
import pytest

from benchmark import spec

CELL, CONFIG, MIX = "ouro-reason-sat", "ouro-2.6b", "reason-loop-sat"
NEW = ["loop_decode_roofline", "loop_pass_ms", "loop_exit_share_pct"]
ALIASES = {"kv_bytes_per_token.tok": {"reader": "kv_bytes_per_token"},
           "paged_loop_roofline": {"reader": "paged_roofline"},
           "loop_exit_share_pct": {"reader": "loop_pass_ms",
                                   "args": {"what": "exit_share_pct"}}}
RETIRED = "loop_exit_share_pct"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
L = 3
HF = {"model_type": "ouro", "num_attention_heads": 4, "hidden_size": 64,
      "head_dim": 16, "intermediate_size": 96, "num_hidden_layers": L,
      "num_key_value_heads": 4, "vocab_size": 512, "rope_theta": 1000000,
      "rms_norm_eps": 1e-6, "sliding_window": None, "total_ut_steps": 4,
      "early_exit_threshold": 1}
OVERRIDES = {"hidden_size": 64, "intermediate_size": 96, "num_layers": L,
             "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
             "vocab_size": 512, "max_seq_len": 256}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 32,
          "block_size": 16, "max_tokens_per_batch": 32,
          "prefill_attn": "xla", "decode_attn": "xla"}


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "Ouro-2.6B")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_uncut():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == {}
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["hidden_size"],
            cfg["total_ut_steps"], cfg["early_exit_threshold"]) \
        == (48, 49152, 2048, 4, 1)
    assert set(cfg["assumed"]) >= {
        "post_sublayer_norms", "norm_between_passes",
        "cache_row_per_pass_and_layer", "exit_gate", "no_bias", "weights",
        "dtype", "kv_pool"}
    assert cfg["engine"] == {
        "max_context": 512, "max_sequences": 16, "num_blocks": 80,
        "block_size": 64, "max_tokens_per_batch": 256,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == spec.Bench().config("phi-2")["policy"]
    assert (cfg["path"], cfg["dtype"], cfg["overrides"]) \
        == ("serve", "bfloat16", {})


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("ouro-2.6b")
    for hf in (_catalog_row()["config"], spec.Bench().config(CONFIG)):
        want = family.program_widths(hf)
        assert {k: getattr(whole, k) for k in want} == want
    assert (whole.rope_theta, whole.rms_norm_eps, whole.use_bias,
            whole.tie_embeddings, whole.mlp_type, whole.activation) \
        == (1e6, 1e-6, False, False, "glu", "silu")
    # ISSUE 39's count at 2 bytes a served parameter: 4.97 GiB
    assert whole.param_count() * 2 / 2**30 == pytest.approx(4.97, abs=0.01)
    # the pool: [192, 5120, 16, 128] twice in bf16 = 7.5 GiB, one array
    # under 2^31 elements
    engine = spec.Bench().config(CONFIG)["engine"]
    slots = engine["num_blocks"] * engine["block_size"]
    one = whole.num_kv_layers * slots * whole.num_kv_heads * whole.head_dim
    assert (whole.num_kv_layers, one < 2**31, 2 * one * 2 / 2**30) \
        == (192, True, 7.5)


def test_the_familys_counts_against_a_hand_count(family):
    a = family.arch(HF)
    layer = 4 * 64 * 64 + 3 * 64 * 96
    assert family.layer_params(a) == layer
    assert family.matmul_params(a) == 4 * L * layer + 64 * 512
    # attention: 4 passes x 3 layers x 12 x 16 x 4 heads x pairs / seq
    pairs = 8 * 9 // 2
    assert family.train_flops_per_token(a, 8) == 6 * (
        4 * L * layer + 64 * 512) + 4 * 12 * 16 * 4 * L * pairs / 8
    assert family.kv_rows(a) == 4 * L
    assert family.kv_bytes_per_token(a) == 4 * L * 2 * 4 * 16 * 2
    assert family.decode_step_bytes(a, 100) == (
        4 * L * layer + 64 * 512) * 2 + 100 * 4 * L * 2 * 4 * 16 * 2
    whole = family.arch(spec.Bench().config(CONFIG))
    assert family.kv_bytes_per_token(whole) == 1_572_864
    # 19.7 GB of layer weights a step (four passes) + 0.2 GB of head
    assert family.decode_step_bytes(whole, 0) == pytest.approx(19.94e9,
                                                               rel=2e-3)


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == []
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert reports >= {
        "start_to_chip_s", "live_seqs_mean", "ragged_tile_fill_pct",
        "itl_p99_ms.moe", "round_p50_ms.moe", "share_ragged_rounds_pct.moe",
        "serve_program_gib.moe", "decode_fwd_ms.moe", "ragged_fwd_ms.moe",
        "serve_idle_pct.moe", "launch_ahead_pct",
        *(set(NEW) | set(ALIASES)) - {RETIRED}}
    # 0.0035 % on the ledger: it tells nothing until passes are skipped, and
    # PR 62 retired the entry; the PR that brings skipping lists it again
    # (the reader and the name's file stay, and the tests below read it)
    assert RETIRED not in {m["name"] for m in bench.doc["per_layer"]}
    # (no place in ``per_layer`` is held here: a later PR appends behind)
    for m in bench.doc["per_layer"]:
        if m["name"] in ("launch_ahead_pct", *NEW, *ALIASES):
            assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
    for name, alias in ALIASES.items():
        assert json.loads(bench._find(
            "metrics", name, (".json",)).read_text()) == alias


def test_the_mix_is_the_issues_grid_and_fits_the_pool():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], len(pairs), mix["trace_seconds"]) \
        == ("closed", 16, 64, 4)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 32, "max": 128}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert mix["clients"] == cfg["max_sequences"]
    assert sum(o for _, o in pairs) / 64 == pytest.approx(128, abs=1)
    worst = sorted((p + o for p, o in pairs), reverse=True)[:16]
    assert worst[0] <= 320 <= cfg["max_context"]
    assert sum(-(-t // cfg["block_size"]) for t in worst) <= cfg["num_blocks"]
    assert 16 * -(-320 // cfg["block_size"]) == cfg["num_blocks"]


# ------------------------------------------------------------ the tiny cell
def _tiny_bench(root, name, engine, policy):
    from . import tiny

    bench = tiny.make_root(root)
    doc = bench.doc
    cfg = {**HF, "source": "tests", "path": "serve", "preset": "ouro-2.6b",
           "overrides": OVERRIDES, "dtype": "float32",
           "engine": {**ENGINE, **engine}, "policy": policy}
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
    return tiny.drive(bench, f"{name}-cell", seed=2**31 + 39)


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    return _tiny_bench(tmp_path_factory.mktemp("bench"), "tiny-ouro", {},
                       {"admission": "none"})


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    bench = spec.Bench()
    # everything the cell lists that needs no device trace (the CPU has
    # none) and no atoms (the xla attention takes none)
    untraced = {x["name"] for x in bench.metrics_of(CELL, "per_layer")
                if x["source"] != "device_trace"} - {
        "start_to_chip_s", "ragged_tile_fill_pct"}
    assert untraced <= set(m), untraced - set(m)
    assert m["serve_tok_s"] > 0 and m["live_seqs_mean"] > 1
    # 4 passes x 3 layers of K and V, 4 heads of 16, float32
    assert m["kv_bytes_per_token.tok"] == 4 * L * 2 * 4 * 16 * 4
    assert 0 < m["launch_ahead_pct"] <= 100
    eng = obs["engine"]
    stats = eng.loop_stats()
    assert stats["exit_pass"][:3] == [0, 0, 0] and stats["exit_pass"][3] > 0
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_the_records_carry_the_passes(tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    launched = [d for d in spans.round_records(obs) if d["program"]]
    assert len(launched) > 10
    assert all((d["passes"], d["kv_rows"]) == (4, 4 * L) for d in launched)


def test_a_tight_pool_evicts_and_the_run_is_still_correct(tmp_path):
    """``preempt_policy: requeue`` under a pool of 6 blocks of 8 tokens:
    evicted streams are prefilled again into fresh blocks, all ``passes x
    layers`` rows of them, and the run's reference check, then held against
    the WHOLE output of such a stream, passes."""
    obs, _m = _tiny_bench(tmp_path, "tiny-ouro-tight",
                          {"num_blocks": 6, "block_size": 8,
                           "max_context": 48},
                          {"admission": "none", "preempt_policy": "requeue"})
    again = [r for r in obs["requests"] if r["evictions"]]
    assert again and all(r["closed"] == "done" for r in again)
    assert obs["correct"] and obs["failed"] == 0


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, live=16, ctx=2000, pass_s=0.009, exit_s=0.0002,
               head_s=0.001, scopes=True, passes=4):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each launching one ``decode_forward`` over ``live`` rows
    with ``ctx`` cached tokens; on the device, per execution, one fusion
    under ``loop_pass`` a pass (the while body runs it ``passes`` times), one
    under ``loop_exit`` and the head's outside both."""
    from benchmark import spans

    cfg = spec.Bench().config(CONFIG)
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.050, 0.061, 0.072, 0.083, 0.094):
        rounds.append((t, t + took, live, 0))
        t += took + 0.001
    stages, host, modules, ops = [], [], [], []
    ran = passes * pass_s + exit_s + head_s
    for i, (t0, t1, *_) in enumerate(rounds):
        data = {"stage": "round", "round": i, "t0": t0 + 1e-4,
                "t1": t1 - 1e-4, "launch_t": t0 + 0.0031, "tokens": live,
                "program": "decode_forward", "n_seqs": live,
                "decode_rows": live, "ctx_tokens": ctx}
        if passes:
            data.update(passes=passes, kv_rows=passes * 48)
        stages.append({"name": "serve/stage", "data": data})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     ["PjitFunction(decode_forward)", at + 0.002, 0.001]]
            modules.append(["jit_decode_forward(7)", at + 0.004, ran])
            start = at + 0.004
            for text, took in (
                    [("%fusion.3 = bf16[16,2048]{1,0} fusion(%x)", pass_s)]
                    * (passes or 1)
                    + [("%fusion.4 = s32[16]{0} fusion(%x)", exit_s),
                       ("%fusion.5 = f32[16,49152]{1,0} fusion(%x)",
                        head_s)]):
                ops.append([text, start, took])
                start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = 'op_name="jit(decode_forward)/'
            return (f'  %fusion.3 = bf16[16,2048]{{1,0}} fusion(%x), '
                    f'metadata={{{path}while/body/loop_pass/while/body/'
                    f'dot_general"}}\n'
                    f'  %fusion.4 = s32[16]{{0}} fusion(%x), '
                    f'metadata={{{path}loop_exit/argmax"}}\n'
                    f'  %fusion.5 = f32[16,49152]{{1,0}} fusion(%x), '
                    f'metadata={{{path}dot_general"}}\n')

    pool = types.SimpleNamespace(shape=(192, 5120, 16, 128),
                                 size=192 * 5120 * 16 * 128,
                                 dtype=np.dtype("float16"))
    engine = types.SimpleNamespace(
        compiled_programs=lambda: {"decode_forward": Compiled()},
        kv=types.SimpleNamespace(k=pool, v=pool))
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_the_loop_readers_on_a_decode_step(family):
    """16 live rows over 2,000 cached tokens: 19.94 GB of weights (four
    passes and the head) + 2,000 x 1.5 MiB of cache = 23.09 GB, 28.2 ms at
    819 GB/s, against an execution of 37.2 ms: 75.8 %. A pass is 9 ms, the
    exit 0.2 of 37.2 ms. At the floor itself (the execution takes the
    bytes' time) the share reads 100 and cannot pass it."""
    bench = spec.Bench()
    obs = traced_obs(family)
    ideal = (19.94e9 + 2000 * 1_572_864) / 819e9
    assert ideal == pytest.approx(28.19e-3, rel=2e-3)
    assert bench.reader("loop_decode_roofline")(obs) == pytest.approx(
        100 * ideal / 0.0372, rel=2e-3)
    assert bench.reader("loop_pass_ms")(obs) == pytest.approx(9.0, rel=1e-6)
    assert bench.reader("loop_exit_share_pct")(obs) == pytest.approx(
        100 * 0.0002 / 0.0372, rel=1e-6)
    exact = family.decode_step_bytes(family.arch(obs["config"]), 2000) / 819e9
    at_floor = traced_obs(family, pass_s=exact / 4, exit_s=0.0, head_s=0.0)
    assert bench.reader("loop_decode_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    # the aliases read the same observation: 192 kernel calls would each
    # read the contexts; here no custom call ran, so the kernel's share has
    # nothing to divide by
    assert bench.reader("paged_loop_roofline")(obs) is None
    assert bench.reader("kv_bytes_per_token.tok")(obs) == 1_572_864


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(tiny_cell, family,
                                                           name):
    """No trace (the CPU); a program without the scopes or the record's
    ``passes`` (the parent, every model whose layers run once): ``None``,
    not 0, and nothing raised."""
    bench = spec.Bench()
    obs, m = tiny_cell
    assert name not in m and bench.reader(name)(obs) is None
    assert bench.reader(name)({**obs, "stages": [], "engine": None}) is None
    parent = traced_obs(family, scopes=False, passes=0)
    assert bench.reader(name)(parent) is None
    # another family on a traced run of a looped program's records
    other = spec.Bench().family({"model_type": "phi"})
    if name == "loop_decode_roofline":
        assert bench.reader(name)({**traced_obs(family),
                                   "family": other}) is None
