"""The serving forwards under the training model's MFU regions (PR 67).

``inference/v2/model.py`` wraps the phases of ``decode_forward`` and
``ragged_forward`` in ``mfu.region_scope`` (``embed``, ``attn``, ``mlp``,
``head``), ``InferenceEngineV2.compiled_programs()`` compiles each ``(program,
rows)`` once and publishes its map as ``<program>@<rows>``, and
``mfu.build_opmap`` says of every instruction its innermost sub-scope and
what it ends in (``root``). Held here for a tiny engine of each layer kind
the model walks (``serve_tiny.KINDS``): every instruction that runs has a
region and ``other`` holds only what no line of the model asked for; every
sub-scope nests in the region it belongs to; the labels changed no program.
"""
import contextlib
import json
import re
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from benchmark import scopes
from deepspeedsyclsupport_tpu.inference.v2 import model as M
from deepspeedsyclsupport_tpu.monitor import mfu
from deepspeedsyclsupport_tpu.monitor import telemetry as tel
from tests.unit import serve_tiny

FORWARDS = ("ragged_forward", "decode_forward")
# the roots that only MOVE data, as ``fwd_move_share_pct`` names them
MOVES = set(json.loads((Path(__file__).parents[2] / "benchmark" / "metrics"
                        / "fwd_move_share_pct.json").read_text())
            ["args"]["roots"])
# the components of an op_name path that are a loop's machinery and nobody's
# label
LOOP = {"while", "body", "cond", "closed_call", "loop_pass"}
# the region each sub-scope nests in (``lm_head`` the head's, the experts'
# the channel mixer's, every other one a token mixer's; ``mhc`` wraps both
# sublayers, the embedding's broadcast and the final sum)
REGION_OF = {"lm_head": ("head",), "mhc": ("attn", "mlp", "embed", "head"),
             **{s: ("mlp",) for s in mfu.SUB_SCOPES if s.startswith("moe_")}}


@pytest.fixture(scope="module")
def programs():
    """kind -> (the engine after one forward of each program, {program:
    (compiled text, its opmap)}), built when first asked."""
    built = {}

    def get(kind):
        if kind not in built:
            eng = serve_tiny.engine(kind)
            built[kind] = eng, {
                name: (c.as_text(), mfu.build_opmap(c.as_text()))
                for name, c in eng.compiled_programs().items()}
        return built[kind]
    return get


def no_line_asked_for(entry):
    """Why an instruction under ``other`` may stand there, or None: the
    short list, a reason each."""
    if entry["root"] in MOVES:
        return "only moves data"
    if entry["category"] == "control":
        return "a loop or a call, whose body's instructions are timed"
    parts = entry["op_name"].split("/")
    if not entry["op_name"]:
        # (the CPU compiler's split of a long reduction into a
        # reduce-window and a reduce, its check that a dynamic-update-slice
        # is in range, a product it rewrote: they carry no path at all)
        return "the compiler's own: no line of the model lowered to it"
    plumbing = all(p in LOOP or p.startswith("jit(") for p in parts[:-1])
    if plumbing and parts[-1] in ("add", "lt"):
        return "a loop's counter and its test"
    if plumbing and parts[-1] in ("iota", "mul"):
        # (``jnp.arange`` over the layers, from the first expert layer on;
        # a looped stack's pool row, pass x layers + layer)
        return "the layer indices a scan is handed"
    return None


@pytest.mark.parametrize("kind", sorted(serve_tiny.KINDS))
def test_every_instruction_has_a_region_and_other_is_what_no_line_asked_for(
        programs, kind):
    _eng, texts = programs(kind)
    assert set(texts) == set(FORWARDS)
    for name, (text, opmap) in texts.items():
        ran = serve_tiny.launched(text) & set(opmap)
        assert len(ran) > 20
        regions = {opmap[i]["region"] for i in ran}
        assert {"embed", "attn", "mlp", "head"} <= regions <= set(mfu.REGIONS)
        stray = {i: opmap[i] for i in ran if opmap[i]["region"] == "other"
                 and not no_line_asked_for(opmap[i])}
        assert not stray, (name, stray)


@pytest.mark.parametrize("kind", sorted(serve_tiny.KINDS))
def test_every_sub_scope_nests_in_the_region_it_belongs_to(programs, kind):
    _eng, texts = programs(kind)
    found = set()
    for name, (text, opmap) in texts.items():
        for i in serve_tiny.launched(text) & set(opmap):
            entry = opmap[i]
            if entry["scope"] is None or entry["region"] == "collective":
                continue    # (the test mesh's all-reduce: by its opcode)
            found.add(entry["scope"])
            assert entry["region"] in REGION_OF.get(entry["scope"],
                                                    ("attn",)), (name, entry)
    assert "lm_head" in found
    assert found >= serve_tiny.SCOPES[kind], found


@pytest.mark.parametrize("kind", sorted(serve_tiny.KINDS))
def test_the_scopes_leave_the_lowered_program_as_it_was(programs, kind):
    """The lowered text of both forwards (``as_text()`` prints no location:
    the text with its metadata stripped) is the same with the regions as
    with ``region_scope`` patched to a null context; the labels are in the
    locations and nowhere else."""
    eng, _texts = programs(kind)
    cfg = eng.config

    def lowered():
        out = {}
        for name, build, impl in (
                ("ragged_forward", M.build_ragged_forward_fn,
                 cfg.prefill_attn),
                ("decode_forward", M.build_decode_forward_fn,
                 cfg.decode_attn)):
            fn = build(eng.model, cfg.block_size, attn_impl=impl)
            (_rows, args), = eng._dispatched[name][1].items()
            low = fn.lower(*args)
            out[name] = low.as_text(), low.as_text(debug_info=True)
        return out

    with mock.patch.object(M, "region_scope",
                           lambda name: contextlib.nullcontext()):
        bare = lowered()
    labelled = lowered()
    for name in FORWARDS:
        assert labelled[name][0] == bare[name][0]
        assert "mfu.attn" in labelled[name][1]
        assert "mfu." not in bare[name][1].replace("mfu.mlp", "")
    # (the dense MLP's ``mfu.mlp`` is ``models/layers.mlp_block``'s own)


# ------------------------------------------------------- the engine publishes
@pytest.fixture(scope="module")
def two_shapes():
    """An engine whose mixed rounds have two static shapes, both run."""
    tel.setup_ledger_store.reset()
    eng = serve_tiny.engine("dense", max_tokens_per_batch=512, max_context=512,
                            num_blocks=96, block_size=16)
    assert [s.rows for s in eng._shapes] == [128, 512]
    eng.put([2], [list(range(3, 3 + 200))])        # a round of 512 rows
    assert set(eng._dispatched["ragged_forward"][1]) == {128, 512}
    return eng


def compiles(phase=None):
    """The set-up ledger's ``compile`` records of the two forwards, as
    ``(program, phase)``: building a program anew leaves a ``trace``, a
    ``lower`` and a ``compile`` record, less what JAX still holds of an
    identical one (on the CPU, beside the ``jit`` that just ran it, all but
    the trace)."""
    return [(r["program"], r["phase"]) for r in tel.setup_ledger()
            if r["kind"] == "compile" and r["program"] in FORWARDS
            and phase in (None, r["phase"])]


def test_compiled_programs_compiles_each_shape_once_and_publishes_its_map(
        two_shapes):
    eng = two_shapes
    before = len(compiles())
    first = eng.compiled_programs()
    built = compiles()[before:]
    # ragged_forward at 128 and 512, decode_forward: each built once
    for phase in ("trace", "lower", "compile"):
        said = [p for p, ph in built if ph == phase]
        assert said.count("ragged_forward") <= 2 \
            and said.count("decode_forward") <= 1
    assert sorted(p for p, ph in built if ph == "trace") == [
        "decode_forward", "ragged_forward", "ragged_forward"]
    again = eng.compiled_programs()
    assert compiles()[before:] == built       # and never again
    assert set(eng._compiled) == {("ragged_forward", 128),
                                  ("ragged_forward", 512),
                                  ("decode_forward", 4)}
    # what it returns is what it always was: the largest shape's whole
    assert type(first["ragged_forward"]).__name__ == "ProgramShapes"
    assert first["ragged_forward"].by_rows == again["ragged_forward"].by_rows
    assert first["decode_forward"] is again["decode_forward"]
    assert first["ragged_forward"].by_rows == [
        eng._compiled["ragged_forward", 128],
        eng._compiled["ragged_forward", 512]]
    # one map a program AND shape, the shapes of one program under two names
    assert eng.published_programs() == {
        "ragged_forward": {128: "ragged_forward@128",
                           512: "ragged_forward@512"},
        "decode_forward": {4: "decode_forward@4"}}
    assert compiles()[before:] == built
    small, large = (mfu.published(f"ragged_forward@{rows}")
                    for rows in (128, 512))
    assert small is not large and small and large
    assert mfu.step_record("ragged_forward@128") == {"rows": 128}
    for opmap in (small, large, mfu.published("decode_forward@4")):
        assert {"attn", "mlp", "head", "embed"} <= {
            e["region"] for e in opmap.values()}
        assert all({"scope", "root"} <= set(e) for e in opmap.values())
    # a shape dispatched since is added, and nothing is compiled twice
    assert mfu.published("ragged_forward@128") is small


def test_a_shape_dispatched_later_is_compiled_when_next_asked(two_shapes):
    eng = two_shapes
    eng.compiled_programs()
    before = len(compiles("trace"))
    args = eng._dispatched["ragged_forward"][1]
    args[64] = args[128]         # as if a third shape had run
    try:
        eng.compiled_programs()
        assert compiles("trace")[before:] == [("ragged_forward", "trace")]
        assert ("ragged_forward", 64) in eng._compiled
        assert eng.published_programs()["ragged_forward"][64] \
            == "ragged_forward@64"
        assert mfu.published("ragged_forward@64")
    finally:
        del args[64], eng._compiled["ragged_forward", 64]
        mfu._PUBLISHED.pop("ragged_forward@64", None)
        mfu._RECORDS.pop("ragged_forward@64", None)


def test_an_engine_that_is_never_asked_publishes_and_compiles_nothing():
    was = dict(mfu._RECORDS)
    eng = serve_tiny.engine("dense")
    assert eng._compiled == {} and mfu._RECORDS == was


# ------------------------------------------------------ the map's two fields
@pytest.mark.parametrize("kind", ["sparse", "mamba"])
def test_scope_is_the_innermost_sub_scope_as_the_benchmarks_parser_says(
        programs, kind):
    """``build_opmap``'s ``scope`` against ``benchmark/scopes.py``'s own
    parser on every instruction of both texts: one parser can serve both."""
    _eng, texts = programs(kind)
    seen = set()
    for text, opmap in texts.values():
        said = scopes.instructions_under(text, mfu.SUB_SCOPES)
        assert said
        for name, entry in opmap.items():
            assert entry["scope"] == said.get(name), (name, entry)
        seen |= set(said.values())
    assert {"moe_route", "moe_experts", "moe_combine", "lm_head"} <= seen
    if kind == "mamba":     # the chunked scan's pieces INSIDE ssm_scan
        assert {"ssm_scan", "ssm_chunk"} <= seen


def test_scope_of_takes_the_innermost_and_only_whole_components():
    assert mfu.scope_of("jit(f)/mfu.attn/ssm_scan/ssm_chunk/dot") \
        == "ssm_chunk"
    assert mfu.scope_of("jit(f)/mfu.mlp/moe_experts/while/body/add") \
        == "moe_experts"
    assert mfu.scope_of("jit(f)/mfu.mlp/not_moe_experts/add") is None
    assert mfu.scope_of("") is None and mfu.scope_of(None) is None


HAND = '''HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,16]) -> f32[16,8] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %convert.3 = f32[8,16]{1,0} convert(%param_0.1)
  ROOT %copy.7 = f32[16,8]{0,1} copy(%convert.3), metadata={op_name="jit(f)/mfu.attn/mla_proj/transpose"}
}

%fused_computation.2 (p: f32[8], q: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %add.1 = f32[8]{0} add(%p, %q)
  %copy.9 = f32[8]{0} copy(%q)
  %bitcast.4 = f32[8]{0} bitcast(%add.1)
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%bitcast.4, %copy.9)
}

%fused_computation.3 (param_0.2: bf16[8,16]) -> bf16[8,128] {
  %param_0.2 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %constant.1 = bf16[] constant(0)
  %convert.4 = bf16[8,16]{1,0:T(8,128)(2,1)} convert(%param_0.2)
  ROOT %pad.2 = bf16[8,128]{1,0:T(8,128)(2,1)} pad(%convert.4, %constant.1), padding=0_0x0_112
}

ENTRY %main.5 (a: f32[8,16], b: bf16[8,16]) -> f32[16,8] {
  %a = f32[8,16]{1,0} parameter(0)
  %b = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%a, %a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/mfu.mlp/moe_route/add"}
  %pad_fusion = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%b), kind=kLoop, calls=%fused_computation.3
  %paged_decode.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/mfu.attn/h1_attn/paged_decode"}
  %while.1 = (s32[], f32[8,16]{1,0}) while(%a), condition=%c, body=%d, metadata={op_name="jit(f)/while"}
  ROOT %copy_fusion.1 = f32[16,8]{0,1} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/mfu.attn/mla_proj/transpose"}
}
'''


def test_root_is_what_a_fusion_ends_in_and_its_own_opcode_elsewhere():
    opmap = mfu.build_opmap(HAND)
    # a fusion whose fused computation ends in a copy: root copy, and its
    # category, region, pass and opcode what they always were
    assert opmap["copy_fusion.1"] == {
        "region": "attn", "pass": "fwd", "category": "fusion",
        "opcode": "fusion", "scope": "mla_proj", "root": "copy",
        "op_name": "jit(f)/mfu.attn/mla_proj/transpose"}
    # a pad fused with its producer's convert, under no scope at all
    assert (opmap["pad_fusion"]["root"], opmap["pad_fusion"]["region"],
            opmap["pad_fusion"]["scope"]) == ("pad", "other", None)
    # through a bitcast and a tuple to what the results are: two kinds
    assert opmap["fusion.2"]["root"] == "add+copy"
    assert opmap["fusion.2"]["scope"] == "moe_route"
    # a Pallas custom call is a custom-call, never a move
    assert opmap["paged_decode.6"]["root"] == "custom-call"
    assert opmap["paged_decode.6"]["scope"] == "h1_attn"
    assert opmap["paged_decode.6"]["category"] == "other"
    assert (opmap["while.1"]["root"], opmap["while.1"]["category"]) \
        == ("while", "control")
    # the instructions INSIDE a fused computation keep their own opcode
    assert opmap["copy.7"]["root"] == "copy" == opmap["copy.7"]["opcode"]
    for entry in opmap.values():
        assert set(entry) == {"region", "pass", "category", "opcode",
                              "op_name", "scope", "root"}


def test_a_compiled_fusion_that_ends_in_a_move_has_that_root():
    def f(x, w):
        with mfu.region_scope("attn"):
            return jnp.tanh(x @ w).T + 1.0

    text = jax.jit(f).lower(jnp.ones((8, 16)), jnp.ones((16, 16))) \
        .compile().as_text()
    opmap = mfu.build_opmap(text)
    # (the CPU compiler ends the turned sum in a copy or in the transpose)
    moved = [e for e in opmap.values()
             if e["opcode"] == "fusion" and e["root"] in MOVES]
    assert moved and all(e["category"] == "fusion" and e["region"] == "attn"
                         for e in moved)
    assert re.search(r"ROOT %\S+ = \S+ (copy|transpose)\(", text)
