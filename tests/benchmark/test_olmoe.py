"""``model_type: olmoe`` at tiny widths, float32, on the CPU: the served path
(chunked prefill, then decode through the cache) and the training forward
against ``benchmark/families/olmoe.py`` on seeded weights, and three wrong
programs that the tolerance must refuse."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity, spec
from benchmark import reference as ref

TINY_OLMOE = {
    "model_type": "olmoe", "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 512, "num_experts": 8,
    "num_experts_per_tok": 3, "norm_topk_prob": False, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "preset": "olmoe-1b-7b",
    "overrides": {"hidden_size": 64, "intermediate_size": 32,
                  "num_layers": 2, "num_heads": 4, "num_kv_heads": 4,
                  "head_dim": 16, "vocab_size": 512, "num_experts": 8,
                  "num_experts_per_tok": 3, "max_seq_len": 128}}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 32,
          "block_size": 16, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
# Both sides are float32 and differ in the order of summation only (the
# reference under "highest", the program at the CPU's default, which is
# float32 too): measured 3.7e-6 logit-std served, 3.2e-6 trained. The three
# wrong programs below measure 1.9, 2.1 and 2.3, so 1e-4 is 27 times what
# rounding gives and four orders under what a misreading gives.
TOL = 1e-4
PROMPTS = ([7, 3, 11, 200, 41, 9, 5], list(range(100, 141)))   # 7 and 41


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(TINY_OLMOE)


def build(**more):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("olmoe-1b-7b", **{
        **TINY_OLMOE["overrides"], "attn_impl": "xla", "dtype": "float32",
        # the training forward drops what overflows an expert's capacity;
        # serving and the reference never do: room for every token
        "capacity_factor": 8.0 / 3.0, **more})
    model.seed = 3
    params = model.init_params()
    # norm scales start at one and a uniform scale commutes with rotary:
    # move every leaf off its init, so that WHERE the norm sits matters
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return model, jax.tree_util.tree_unflatten(tree, [
        x + 0.2 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])


def served_errors(model, params, family):
    """Worst row error of the served path over two requests, one shorter
    and one longer than ``max_tokens_per_batch`` (3 chunks), 6 decode steps
    each, against the reference's forward of the whole sequence."""
    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)

    engine = InferenceEngineV2(
        model, params, dtype="float32",
        topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
        **ENGINE)
    arch = family.arch(TINY_OLMOE)
    worst = 0.0
    for uid, prompt in enumerate(PROMPTS):
        logits, tokens = parity.served_logits(engine, uid, prompt, 6)
        want = family.sequence_logits(
            arch, params, jnp.asarray(prompt + tokens, jnp.int32))
        worst = max(worst, float(parity.row_errors(
            logits, np.asarray(want)[-len(logits):]).max()))
    return worst


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config

    published = {
        "model_type": "olmoe", "hidden_size": 2048, "intermediate_size": 1024,
        "num_hidden_layers": 16, "num_attention_heads": 16,
        "num_key_value_heads": 16, "vocab_size": 50304, "num_experts": 64,
        "num_experts_per_tok": 8, "norm_topk_prob": False,
        "rms_norm_eps": 1e-05, "rope_theta": 10000}
    cfg = get_config("olmoe-1b-7b")
    want = family.program_widths(published)
    assert {k: getattr(cfg, k) for k in want} == want
    assert cfg.head_dim == 128 and cfg.max_seq_len == 4096
    assert not cfg.tie_embeddings and cfg.activation == "silu"
    # 6.92 B parameters, 1.3 B of them met by a token
    assert cfg.param_count() == pytest.approx(6.92e9, rel=2e-3)


def test_served_prefill_chunks_then_decode_match_the_reference(family):
    model, params = build()
    assert served_errors(model, params, family) < TOL


def test_training_forward_matches_the_reference(family):
    model, params = build()
    ids = np.random.default_rng(0).integers(0, 512, (2, 24)).astype(np.int32)
    got = np.asarray(model.apply(params, jnp.asarray(ids)))
    arch = family.arch(TINY_OLMOE)
    for row, have in zip(ids, got):
        want = np.asarray(family.sequence_logits(arch, params,
                                                 jnp.asarray(row)))
        assert float(parity.row_errors(have, want).max()) < TOL


def _attention_with(norm_place):
    """The reference's attention with the QK-norm somewhere it is NOT."""
    def attention(a, p, x):
        s = x.shape[0]
        h, d = a["num_heads"], a["head_dim"]
        pos = jnp.arange(s)
        q, k = x @ p["wq"], x @ p["wk"]
        v = (x @ p["wv"]).reshape(s, h, d)
        if norm_place == "per_head":       # each head normed by itself
            q, k = ((t.reshape(s, h, d) / jnp.sqrt(jnp.square(
                t.reshape(s, h, d)).mean(-1, keepdims=True) + a["norm_eps"])
                * n["scale"].reshape(h, d))
                for t, n in ((q, p["q_norm"]), (k, p["k_norm"])))
            q, k = ref.rope(a, q, pos), ref.rope(a, k, pos)
        else:                              # the norm AFTER rotary
            q, k = (ref.rms_norm(n, ref.rope(a, t.reshape(s, h, d), pos)
                                 .reshape(s, h * d), a["norm_eps"])
                    .reshape(s, h, d)
                    for t, n in ((q, p["q_norm"]), (k, p["k_norm"])))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                          v).reshape(s, h * d) @ p["wo"]
    return attention


@pytest.mark.parametrize("wrong", ["renormalised_topk", "per_head_norm",
                                   "norm_after_rotary"])
def test_a_wrong_program_fails_the_tolerance(family, monkeypatch, wrong):
    """Each is a plausible misreading of the architecture. Held against the
    served path, it must come out far beyond ``TOL`` (the served path is
    right, so the misreading is put on the reference's side, or, for the
    top-k weights, into the program's own config)."""
    if wrong == "renormalised_topk":
        model, params = build(norm_topk_prob=True)
    else:
        model, params = build()
        monkeypatch.setattr(family, "attention", _attention_with(
            "per_head" if wrong == "per_head_norm" else "after_rotary"))
    assert served_errors(model, params, family) > 100 * TOL


# ------------------------------------------------- the cell's own readers
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A tiny ``olmoe`` cell beside ``tests/benchmark/tiny.py``'s, reporting
    what ``olmoe-chat-sat`` reports, driven once on the CPU."""
    import json

    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc = bench.doc
    cfg = {**TINY_OLMOE, "source": "tests", "path": "serve",
           "dtype": "float32", "engine": {**ENGINE, "max_tokens_per_batch": 32},
           "policy": {"admission": "none"}}
    (root / "extra" / "configs" / "tiny-olmoe.json").write_text(
        json.dumps(cfg))
    doc["configs"].append({"name": "tiny-olmoe", "source": "tests",
                           "reduced": [], "why": "tiny",
                           "file": "extra/configs/tiny-olmoe.json"})
    doc["workloads"].append({"name": "tiny-olmoe-cell", "chips": 1,
                             "config": "tiny-olmoe", "why": "tiny",
                             "traffic": "tiny-closed"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "olmoe-chat-sat" in m.get("workloads", ()):
            m["workloads"].append("tiny-olmoe-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, "tiny-olmoe-cell", seed=2**31 + 11)


def test_the_cell_runs_is_checked_and_counts_its_routing(tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    assert m["serve_tok_s"] > 0 and m["itl_p99_ms.moe"] > 0
    # 8 experts, 3 a token, seeded noise: near uniform, never below 1
    assert 1.0 <= m["expert_load_max_over_mean"] < 2.0
    stats = obs["engine"].moe_stats()
    assert (stats["load"].sum(1) == 3 * stats["live_tokens"]).all()
    # the traced readers have nothing to read off the chip: left out
    assert "moe_share_pct" not in m and "moe_roofline" not in m


def test_a_routed_pad_row_voids_the_load_metric(tiny_cell, capsys):
    obs, _m = tiny_cell
    reader = spec.Bench().reader("expert_load_max_over_mean")
    stats = obs["engine"].moe_stats()

    class Engine:
        def moe_stats(self):
            load = stats["load"].copy()
            load[1, 0] += 1
            return {**stats, "load": load}

    assert reader({**obs, "engine": Engine()}) is None
    assert "pad row" in capsys.readouterr().err
    assert reader({**obs, "engine": object()}) is None    # no counter at all


def test_the_scopes_are_found_in_the_compiled_programs(tiny_cell):
    """What the traced readers stand on: every scope of the expert MLP
    names at least one instruction of each forward program's own text."""
    from benchmark import scopes

    obs, _m = tiny_cell
    labels = ("moe_route", "moe_experts", "moe_combine")
    programs = obs["engine"].compiled_programs()
    assert {"ragged_forward", "decode_forward"} <= set(programs)
    for name, compiled in programs.items():
        found = scopes.instructions_under(compiled.as_text(), labels)
        assert set(found.values()) == set(labels), (name, found)
    assert scopes.scoped_ops({"trace": None, "engine": obs["engine"]},
                             labels) is None


def test_scoped_ops_picks_a_programs_instructions_out_of_a_trace():
    from benchmark import scopes

    text = '''
  %fusion.3 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(decode_forward)/while/body/moe_experts/ragged_dot" source_file="x.py"}
  ROOT %add.7 = bf16[8,4]{1,0} add(%b, %c), metadata={op_name="jit(decode_forward)/while/body/moe_combine/add"}
  %dot.1 = f32[8,8]{1,0} dot(%x, %y), metadata={op_name="jit(decode_forward)/while/body/attn/dot_general"}
'''
    assert scopes.instructions_under(text, ("moe_experts", "moe_combine")) \
        == {"fusion.3": "moe_experts", "add.7": "moe_combine"}

    class Compiled:
        def as_text(self):
            return text

    class Engine:
        def compiled_programs(self):
            return {"decode_forward": Compiled()}

    module = "jit_decode_forward(123)"
    trace = {"host": [], "devices": {"/device:TPU:0": {
        "modules": [[module, 1.0, 1.0]],
        "ops": [["%fusion.3 = bf16[8,4]{1,0} fusion(%a)", 1.1, 0.2],
                ["%dot.1 = f32[8,8]{1,0} dot(%x, %y)", 1.4, 0.1],
                # the same name in ANOTHER program's execution is not it
                ["%fusion.3 = bf16[8,4]{1,0} fusion(%a)", 3.0, 0.2]]}}}
    obs = {"trace": trace, "engine": Engine()}
    assert scopes.scoped_ops(obs, ("moe_experts",)) \
        == [("moe_experts", "decode_forward", 1.1, 0.2)]
    # the TPU compiler's own custom calls carry no scope: found by name
    call = ('%ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", '
            'metadata={op_name="ragged-dot-none"}')
    other = call.replace("ragged-dot-none.2", "paged_decode.7")
    trace["devices"]["/device:TPU:0"]["ops"] += [[call, 1.6, 0.3],
                                                 [other, 1.95, 0.01]]
    assert scopes.scoped_ops(obs, ("moe_experts",),
                             (("ragged-dot", "moe_experts"),))[1:] \
        == [("moe_experts", "decode_forward", 1.6, 0.3)]


def test_expert_work_is_a_hand_count(family):
    reader = spec.Bench()._module("metrics", "moe_roofline")
    arch = {"hidden_size": 4, "intermediate_size": 3}
    # 10 rows, each through three 4x3 matrices (2 FLOPs a weight); 7
    # expert-layers' weights of 3 x 12 numbers read once, 10 rows of 4 in
    # and out, 2 bytes each
    assert reader.expert_work(arch, touched=7, rows=10) \
        == (10 * 3 * 2 * 12, (7 * 36 + 2 * 10 * 4) * 2)
    # the published sizes at a full decode step: 32 tokens x 8 experts in
    # each of 10 layers, 631 expert-layers touched of 640 -> 7.94 GB of
    # weights beside 0.02 GB of rows, weight-bound
    published = family.arch({**TINY_OLMOE, "hidden_size": 2048,
                             "intermediate_size": 1024,
                             "num_hidden_layers": 10,
                             "num_experts_per_tok": 8})
    assert reader.expert_layers(published) == 10
    assert reader.expert_layers({"num_layers": 6, "num_dense_layers": 2}) == 4
    flops_, nbytes = reader.expert_work(published, touched=631,
                                        rows=32 * 8 * 10)
    assert nbytes == pytest.approx(7.96e9, rel=1e-3)
    assert flops_ / 197e12 < nbytes / 819e9


# --------------------- the expert readers on a chip's share of the experts
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OLMOE_D10 = {"hidden_size": 2048, "intermediate_size": 1024,
             "num_layers": 10, "num_experts_per_tok": 8}
GEMM = ('%ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


def traced_obs(tokens, touched, gemm_s, moe_rows=None,
               program="decode_forward"):
    """``obs`` of a traced run of three rounds, each launching one forward
    of ``tokens`` live tokens whose grouped GEMMs took ``gemm_s`` on the
    device; the counts of a forward ride on the record AFTER its own."""
    import types

    from benchmark import spans

    offset, rounds, t = 5.0, [], 100.0
    for took in (0.030, 0.041, 0.052, 0.063, 0.074):    # no two alike
        rounds.append((t, t + took, 32, 0))
        t += took + 0.001
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        data = {"stage": "round", "round": i, "t0": t0 + 1e-4,
                "t1": t1 - 1e-4, "launch_t": t0 + 0.0031, "tokens": tokens,
                "program": program, "moe_touched": touched}
        if moe_rows is not None:
            data["moe_rows"] = moe_rows
        stages.append({"name": "serve/stage", "data": data})
        if 1 <= i <= 3:                                  # the traced ones
            host += [[spans.ROUND_SPAN, t0 + offset, t1 - t0],
                     [f"PjitFunction({program})", t0 + offset + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", t0 + offset + 0.004, 0.02])
            ops.append([GEMM, t0 + offset + 0.005, gemm_s])

    class Compiled:
        def as_text(self):
            return ""

    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": {}, "peaks": V5E,
            "family": types.SimpleNamespace(arch=lambda cfg: OLMOE_D10)}


def ideal_s(touched, rows):
    """The hand count at OLMoE's widths: the larger of the GEMMs' FLOPs at
    197 TFLOP/s and of the touched weights + the rows in and out, bf16, at
    819 GB/s."""
    return max(rows * 6 * 2048 * 1024 / 197e12,
               (touched * 3 * 2048 * 1024 + 2 * rows * 2048) * 2 / 819e9)


@pytest.mark.parametrize("case", ["decode_round_every_expert_held",
                                  "chunk_round_every_expert_held",
                                  "chunk_round_a_quarter_held",
                                  "a_quarter_held_and_not_counted"])
def test_moe_roofline_counts_the_rows_of_the_experts_held(case):
    read = spec.Bench().reader("moe_roofline")
    if case == "decode_round_every_expert_held":
        # no moe_rows on the record: 32 tokens x 8 x 10 layers; the rows
        # were ONE layer's until PR 32, which read 0.24 % less here
        got = read(traced_obs(tokens=32, touched=631, gemm_s=0.016))
        assert got == pytest.approx(100 * ideal_s(631, 2560) / 0.016)
        assert got / (100 * ideal_s(631, 256) / 0.016) == pytest.approx(
            1.0024, abs=2e-4)
    elif case == "chunk_round_every_expert_held":
        # 768 tokens: 61,440 rows, 0.5 GB beside 8.05 GB of weights: 5.6 %
        # over one layer's rows, still bound by the bytes (10.4 ms, the
        # FLOPs 3.9)
        got = read(traced_obs(768, 640, 0.018, program="ragged_forward"))
        assert got == pytest.approx(100 * ideal_s(640, 61440) / 0.018)
        assert ideal_s(640, 61440) == pytest.approx(0.01044, rel=1e-3)
        assert got / (100 * ideal_s(640, 6144) / 0.018) == pytest.approx(
            1.056, abs=2e-3)
    elif case == "chunk_round_a_quarter_held":
        # 16 of 64 experts held: 160 expert-layers touched, a quarter of
        # the rows, counted on the device: 2.61 ms of bytes in 3.0
        got = read(traced_obs(768, 160, 0.003, moe_rows=15360,
                              program="ragged_forward"))
        assert got == pytest.approx(100 * ideal_s(160, 15360) / 0.003)
        assert 85 < got < 100
    else:
        # the same forward read without its count: every token's 8 rows,
        # four times what went through the experts here, 3.9 ms of FLOPs in
        # 3.0: the reading the driver refuses (over 105), which is why a
        # program that holds a share MUST write moe_rows
        got = read(traced_obs(768, 160, 0.003, program="ragged_forward"))
        assert got == pytest.approx(100 * ideal_s(160, 61440) / 0.003)
        assert got > 105


@pytest.mark.parametrize("held", [None, [2, 3]])
def test_expert_load_is_over_the_columns_the_chip_holds(held, capsys):
    """``load`` is over the router's whole width either way and is held to
    its invariant there; with ``held`` the busiest and the mean expert are
    this chip's own."""
    import types

    read = spec.Bench().reader("expert_load_max_over_mean")
    load = np.array([[6, 2, 3, 1], [3, 3, 5, 1]])     # 2 layers, 4 experts

    def obs(load):
        stats = {"load": load, "live_tokens": 6}
        if held is not None:
            stats["held"] = held
        return {"engine": types.SimpleNamespace(moe_stats=lambda: stats),
                "config": {}, "family": types.SimpleNamespace(
                    arch=lambda cfg: {"num_experts_per_tok": 2})}

    want = (6 / 3 + 5 / 3) / 2 if held is None else (3 / 2 + 5 / 3) / 2
    assert read(obs(load)) == pytest.approx(want)
    # a row too many in a column the chip does NOT hold still voids it
    assert read(obs(load + np.array([[1, 0, 0, 0], [0, 0, 0, 0]]))) is None
    assert "pad row" in capsys.readouterr().err
