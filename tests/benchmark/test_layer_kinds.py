"""A reader of a KIND of layer asks the cell's family what layers of the kind
it has (``benchmark.reference.layer_kind``): what each of the five families
says, that a family which says nothing gives nothing to read, and what the
next ``model_config`` PR does to list its readers: a family file, a cell,
the cell's name appended to the accepted entries, and nothing else."""
import json
import types

import pytest

from benchmark import reference, spec

from . import tiny

BENCH = spec.Bench()
STATE = ("state_share_pct", "state_decode_roofline", "state_chunk_roofline")
SELECT = ("select_share_pct", "select_pick_share_pct",
          "select_score_roofline", "select_prefill_roofline",
          "select_decode_roofline")
# model_type -> the kinds its module says
SAYS = {"nemotron_h": {"recurrent_state"}, "brumby": {"recurrent_state"},
        "solar_open2": {"recurrent_state"}, "KeyeVL2": {"selection"},
        "minicpm_sala": {"recurrent_state", "selection"}}
FAMILIES = sorted({BENCH._config_file(c["name"])["model_type"]
                   for c in BENCH.doc["configs"]})
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def family_of(model_type):
    return BENCH.family({"model_type": model_type})


@pytest.mark.parametrize("model_type", FAMILIES)
def test_what_a_family_says_of_its_kinds_has_every_part(model_type):
    family = family_of(model_type)
    said = {k for k in reference.LAYER_KINDS
            if reference.layer_kind(family, k) is not None}
    assert said == SAYS.get(model_type, set())
    state = reference.layer_kind(family, "recurrent_state")
    if state:
        assert set(state) - {"share_kernels", "step_kernels",
                             "chunk_kernels", "slot_layer_bytes"} == {
            "share_scopes", "step_scopes", "step_pieces", "chunk_scopes",
            "chunk_work"}
        assert set(state["step_scopes"]) | set(state["chunk_scopes"]) \
            <= set(state["share_scopes"]) | {state["chunk_scopes"][0]}
        assert callable(state["chunk_work"])
    select = reference.layer_kind(family, "selection")
    if select:
        assert set(select) == {"scopes", "kernels", "roles", "score",
                               "prefill", "rows"}
        assert set(select["roles"]) == {"score", "select", "attend"}
        for labels in select["roles"].values():
            assert set(labels) <= set(select["scopes"])
        for part in ("score", "prefill", "rows"):
            assert set(select[part]) == {"scopes", "kernels", "work"}
            assert callable(select[part]["work"])


@pytest.mark.parametrize("model_type,name", [
    (t, n) for t in FAMILIES for kind, names in (
        ("recurrent_state", STATE), ("selection", SELECT))
    if kind not in SAYS.get(t, ()) for n in names])
def test_a_family_without_the_kind_reads_none(model_type, name):
    """Not 0, and nothing raised, though the trace holds device time and the
    engine a state pool: the family's say is what a reader reads by. (A
    family that has the kind is read in its own file's tests.)"""
    obs = synthetic_obs(family_of(model_type), {"model_type": model_type})
    assert BENCH.reader(name)(obs) is None


def test_a_kind_nobody_knows_and_a_module_without_the_function():
    assert reference.layer_kind(types.SimpleNamespace(), "selection") is None
    assert reference.layer_kind(family_of("brumby"), "convolution") is None


# ------------------------------- what the next model_config PR does to join
# a family whose every layer keeps a state, as its module says: the tiny
# mixtral reference with the new part appended (the forward is not run here)
NEW_STATE_FAMILY = tiny.NEW_FAMILY + '''

def layer_kinds():
    """Every layer keeps a recurrent state of 4 KiB a sequence."""
    return {"recurrent_state": {
        "share_scopes": ("mix_proj", "mix_scan"),
        "step_scopes": ("mix_scan",), "step_pieces": "mix_pieces",
        "slot_layer_bytes": lambda obs: 4096,
        "chunk_scopes": ("mix_chunk",),
        "chunk_work": lambda obs: lambda record: (
            (record["mix_rows"] * 1000, record["mix_rows"] * 64)
            if record.get("mix_rows") else None)}}
'''
JOINS = STATE + ("live_seqs_mean", "state_bytes_per_seq")


def joined_root(tmp_path):
    """``tiny.make_root`` plus one family file, one configuration, one cell
    and that cell's name appended to accepted entries' lists."""
    doc = tiny.make_root(tmp_path).doc
    (tmp_path / "extra" / "families" / "mixstate.py").write_text(
        NEW_STATE_FAMILY)
    (tmp_path / "extra" / "configs" / "tiny-mixstate.json").write_text(
        json.dumps({**tiny.TINY_MIXTRAL, "model_type": "mixstate"}))
    doc["configs"].append({
        "name": "tiny-mixstate", "source": "tests", "reduced": [],
        "why": "tiny", "file": "extra/configs/tiny-mixstate.json"})
    doc["workloads"].append({
        "name": "mixstate-cell", "config": "tiny-mixstate", "chips": 1,
        "traffic": "tiny-closed", "why": "tiny"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in (*JOINS, "serve_tok_s"):
            m["workloads"].append("mixstate-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Bench(tmp_path)


def synthetic_obs(family, cfg, program="decode_forward", live=4):
    """Five rounds, the middle three traced, each launching ``program``: a
    projection's fusion (3 ms), the state step's (2 ms), a piece's (1 ms, in
    a mixed round) and an MLP's under no scope (4 ms)."""
    from benchmark import spans

    ragged = program == "ragged_forward"
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.050, 0.061, 0.072, 0.083, 0.094):
        rounds.append((t, t + took, live, 0))
        t += took + 0.001
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        stages.append({"name": "serve/stage", "data": {
            "stage": "round", "round": i, "t0": t0 + 1e-4, "t1": t1 - 1e-4,
            "launch_t": t0 + 0.0031, "tokens": live, "program": program,
            "n_seqs": live, "decode_rows": live, "mix_pieces": 2 * live,
            "mix_rows": 100 if ragged else 0}})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", at + 0.004, 0.045])
            start = at + 0.005
            for n, took in ((1, 0.003), (2, 0.002),
                            (3, 0.001 if ragged else 0), (4, 0.004)):
                if took:
                    ops.append([f"%fusion.{n} = f32[8,4]{{1,0}} fusion(%x)",
                                start, took])
                    start += took

    class Compiled:
        def as_text(self):
            path = f'op_name="jit({program})/while/body/'
            return "".join(
                f'  %fusion.{n} = f32[8,4]{{1,0}} fusion(%x), '
                f'metadata={{{path}{scope}"}}\n' for n, scope in (
                    (1, "mix_proj/dot_general"), (2, "mix_scan/mul"),
                    (3, "mix_scan/mix_chunk/dot_general"),
                    (4, "dot_general")))

    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()},
        kv=types.SimpleNamespace(),
        state_stats=lambda: {"bytes_per_slot": 8192, "slots": 4,
                             "slots_live": live, "dtype": "float32",
                             "layers": 2})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_a_new_family_says_its_kind_and_its_cell_joins(tmp_path):
    """No reader, no alias, no entry, no edit to ``spec.py`` or to a test:
    the accepted ``state_*`` entries read the new cell through what its
    family's module says."""
    bench = joined_root(tmp_path)
    assert bench.problems() == []
    assert len(bench.doc["per_layer"]) == len(BENCH.doc["per_layer"]) + 1
    reports = {m["name"] for m in bench.metrics_of("mixstate-cell",
                                                   "per_layer")}
    assert set(JOINS) <= reports
    cfg = bench.config("tiny-mixstate")
    family = bench.family(cfg)
    # a decode step: 5 ms of 9 under the family's scopes; 8 pieces of 4 KiB
    # read and written against 2 ms under ``mix_scan``
    obs = synthetic_obs(family, cfg)
    assert bench.reader("state_share_pct")(obs) == pytest.approx(
        100 * 0.005 / 0.009, rel=1e-6)
    assert bench.reader("state_decode_roofline")(obs) == pytest.approx(
        100 * (2 * 8 * 4096 / 819e9) / 0.002, rel=1e-6)
    assert bench.reader("state_chunk_roofline")(obs) is None
    assert bench.reader("state_bytes_per_seq")(obs) == 8192
    # a mixed round: the family's count of the pieces against 1 ms
    mixed = synthetic_obs(family, cfg, "ragged_forward")
    floor = max(100 * 1000 / 197e12, 100 * 64 / 819e9)
    assert bench.reader("state_chunk_roofline")(mixed) == pytest.approx(
        100 * floor / 0.001, rel=1e-6)
    assert bench.reader("state_decode_roofline")(mixed) is None
    # the same trace under the tiny family that says nothing: nothing
    silent = bench.family(bench.config("tiny-moe-serve"))
    for name in STATE:
        assert bench.reader(name)({**obs, "family": silent}) is None


def test_the_engine_is_asked_for_its_programs_once_a_run(tmp_path):
    """``engine.compiled_programs()`` lowers and compiles every program anew
    each time it is asked (tens of seconds at a cell's size): however many
    readers want a scope, ``scopes.compiled_programs`` asks once, and again
    only for another engine."""
    from benchmark import scopes

    bench = joined_root(tmp_path)
    cfg = bench.config("tiny-mixstate")
    obs = synthetic_obs(bench.family(cfg), cfg)
    asked, programs = [], obs["engine"].compiled_programs

    def counting():
        asked.append(1)
        return programs()
    obs["engine"].compiled_programs = counting
    for name in STATE:
        bench.reader(name)(obs)
    assert len(asked) == 1
    assert scopes.instructions_under(
        scopes.instruction_paths(obs)["decode_forward"], ("mix_scan",)) \
        == {"fusion.2": "mix_scan", "fusion.3": "mix_scan"}
    other = types.SimpleNamespace(**vars(obs["engine"]))
    assert set(scopes.compiled_programs({**obs, "engine": other})) \
        == {"decode_forward"}
    assert len(asked) == 2
