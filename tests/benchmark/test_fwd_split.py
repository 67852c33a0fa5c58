"""The serving forwards' device time by MFU region
(``benchmark/metrics/fwd_split_pct.py`` and its six aliases): on a hand-made
trace where every number can be counted on fingers, with hand-made maps, and
on three rounds of ``olmoe-chat-sat``'s traced tail recorded on the v5e
(``data/fwd_split_v5e.json``: two shapes of ``ragged_forward``, the decode
step, the sampler and the key's split, with the ``round`` records of those
rounds and the published maps of the instructions that occur; op texts cut
to the name and the opcode's stem)."""
import json
from pathlib import Path

import pytest

from benchmark import scopes, spec, trace
from benchmark.metrics import fwd_split_pct as F
from benchmark.metrics import train_step_split_ms
from deepspeedsyclsupport_tpu.monitor import mfu

BENCH = spec.Bench()
DOC = BENCH.doc
PLANE = "/device:TPU:0"
EIGHT = ["phi2-decode-sat", "olmoe-chat-sat", "nemo3-reason-sat",
         "ouro-reason-sat", "brumby-rollout-sat", "cmdaplus-rag-sat",
         "solar2-agent-sat", "falconh1-chat-sat"]
FIVE = ["xing4-docs-sat", "dsv2-answers-sat", "keye-video-sat",
        "sala-docs-sat", "glm5-docs-sat"]
MOVES = ["copy", "transpose", "pad", "slice", "dynamic-slice",
         "dynamic-update-slice", "concatenate", "broadcast", "reshape",
         "bitcast", "gather", "scatter", "copy-start", "copy-done"]
# entry -> (the reader's arguments, the end-to-end metric it moves, its cells)
SIX = {
    "mlp_share_pct": ({"regions": ["mlp"]}, "serve_tok_s", EIGHT),
    "mlp_share_pct.p95": ({"regions": ["mlp"]}, "itl_p95_ms", FIVE),
    "fwd_other_share_pct": ({"regions": ["other", "unmapped"]},
                            "serve_tok_s", EIGHT),
    "fwd_other_share_pct.p95": ({"regions": ["other", "unmapped"]},
                                "itl_p95_ms", FIVE),
    "fwd_move_share_pct": ({"roots": MOVES}, "serve_tok_s", EIGHT),
    "fwd_move_share_pct.p95": ({"roots": MOVES}, "itl_p95_ms", FIVE),
}


def read(name, obs):
    return BENCH.reader(name)(obs)


# ------------------------------------------------------------ by hand
class Engine:
    """What the reader asks of an engine: the names its maps stand under."""

    def __init__(self, names):
        self.names = names

    def published_programs(self):
        return self.names


def entry(region, root="fusion"):
    return {"region": region, "root": root, "scope": None}


# ``fusion.1`` and ``copy.2`` stand in BOTH shapes of ragged_forward, under
# different regions: the compiler numbers each shape's instructions anew
MAPS = {
    "ragged_forward@128": {"fusion.1": entry("attn", "convolution"),
                           "copy.2": entry("other", "copy"),
                           "paged.3": entry("attn", "custom-call")},
    "ragged_forward@512": {"fusion.1": entry("mlp", "convolution"),
                           "copy.2": entry("attn", "copy"),
                           "pad.4": entry("mlp", "pad")},
    "decode_forward@4": {"fusion.1": entry("head", "convolution"),
                         "fusion.5": entry("embed", "gather"),
                         "while.9": entry("other", "while")},
}
NAMES = {"ragged_forward": {128: "ragged_forward@128",
                            512: "ragged_forward@512"},
         "decode_forward": {4: "decode_forward@4"}}


def op(name, kind="fusion", mark=""):
    return f"%{name} = bf16[8,128]{{1,0}} {kind}(bf16[8] %x){mark}"


def hand_made(monkeypatch, names=NAMES, maps=MAPS):
    """Three rounds on a clock that starts at 1 s: ragged_forward at 128
    rows, at 512 rows, decode_forward, the sampler behind each; the harness's
    clock is the trace's less 100 s."""
    for name, opmap in maps.items():
        monkeypatch.setitem(mfu._PUBLISHED, name, opmap)
    kernel = ", " + trace.KERNEL_MARK
    modules, ops, host, rounds, stages = [], [], [], [], []
    plan = (("ragged_forward", 11, 128, [
                (op("fusion.1"), 0.000, 0.006),
                (op("paged.3", "custom-call", kernel), 0.006, 0.002),
                (op("copy.2", "copy"), 0.008, 0.002)]),
            ("ragged_forward", 22, 512, [
                (op("fusion.1"), 0.000, 0.010),
                (op("copy.2", "copy"), 0.010, 0.004),
                # (started while the copy runs: the latest started owns)
                (op("pad.4", "pad"), 0.012, 0.006)]),
            ("decode_forward", 33, 4, [
                (op("while.9", "while"), 0.000, 0.008),   # a container
                (op("fusion.5"), 0.000, 0.001),
                (op("fusion.1"), 0.001, 0.004),
                (op("zzz.7"), 0.005, 0.003)]))            # in no map
    for k, (program, hashed, rows, timed) in enumerate(plan):
        t = 1.0 + 0.1 * k
        last = max(s + d for _x, s, d in timed)
        modules.append([f"jit_{program}({hashed})", t, last])
        ops += [[text, t + s, d] for text, s, d in timed]
        modules.append(["jit_sample_rows(5)", t + last + 0.001, 0.001])
        ops.append([op("fusion.1"), t + last + 0.001, 0.001])
        # the round on the harness's clock, its span and the forward's
        # launch in the trace, the session's record of it
        h0, h1 = t - 100.002, t - 100.0 + 0.05
        rounds.append((h0, h1, 1, k))
        host.append(["bench/serve_step", h0 + 100.0, h1 - h0])
        host.append([f"PjitFunction({program})", h0 + 100.0005, 0.0005])
        host += [[trace.LAUNCH, h0 + 100.0008, 1e-5],
                 [trace.LAUNCH, h0 + 100.03, 1e-5]]
        host.append(["PjitFunction(sample_rows)", h0 + 100.0295, 0.001])
        stages.append({"name": "serve/stage", "data": {
            "stage": "round", "round": k, "t0": h0 + 1e-5, "t1": h1 - 1e-5,
            "launch_t": h0 + 0.001, "program": program, "rows": rows}})
    host.append([trace.WINDOW_SPAN, 0.99, 0.4])
    host.sort(key=lambda e: e[1])
    return {"trace": {"devices": {PLANE: {"modules": modules, "ops": ops}},
                      "host": host},
            "trace_window": (0.99, 1.39), "rounds": rounds, "stages": stages,
            "engine": Engine(names), "compiled_programs": None}


@pytest.fixture
def hand(monkeypatch):
    monkeypatch.setattr(scopes, "compiled_programs", lambda obs: {})
    return hand_made(monkeypatch)


def test_every_instant_has_one_owner_and_the_owners_resum_to_the_busy_time(
        hand):
    table = F.split(hand)
    tr = hand["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, PLANE), *hand["trace_window"])
    assert busy == pytest.approx(0.010 + 0.018 + 0.008 + 0.003)
    assert table["busy_s"] == pytest.approx(busy)
    assert sum(table["owners"].values()) == pytest.approx(busy)
    assert table["forwards_s"] == pytest.approx(busy - 0.003)
    # each execution by ITS shape's map: fusion.1 is attention's at 128 rows,
    # the MLP's at 512, the head's in the decode step
    assert table["owners"] == pytest.approx({
        "attn": 0.006 + 0.002 + 0.002,           # 128: fusion.1, paged.3;
                                                 # 512: copy.2 until the pad
        "other": 0.002,                          # 128: copy.2
        "mlp": 0.010 + 0.006,                    # 512: fusion.1, pad.4
        "embed": 0.001, "head": 0.004,
        "unmapped": 0.003,                       # zzz.7
        "sample_rows": 0.003})                   # a program of its own
    assert table["roots"]["attn"] == pytest.approx(
        {"convolution": 0.006, "custom-call": 0.002, "copy": 0.002})
    assert table["roots"]["mlp"] == pytest.approx(
        {"convolution": 0.010, "pad": 0.006})
    assert table["roots"]["unmapped"] == pytest.approx({"-": 0.003})
    for row in table["roots"].values():
        assert "while" not in row               # a container owns nothing


def test_the_six_entries_read_their_shares_of_the_busy_time(hand):
    busy = 0.039
    assert read("mlp_share_pct", hand) == pytest.approx(100 * 0.016 / busy)
    # what no line asked for AND what the map does not know
    assert read("fwd_other_share_pct", hand) == pytest.approx(
        100 * (0.002 + 0.003) / busy)
    # copies and the pad, whatever their region; a Pallas call never
    assert read("fwd_move_share_pct", hand) == pytest.approx(
        100 * (0.002 + 0.002 + 0.006 + 0.001) / busy)
    for name in SIX:
        assert read(name, hand) == read(name.replace(".p95", ""), hand)


def test_an_execution_is_read_by_its_own_shapes_map_and_one_map_would_not_do(
        hand, monkeypatch):
    right = F.split(hand)["owners"]
    assert (right["attn"], right["mlp"]) == pytest.approx((0.010, 0.016))
    # ONE map for both shapes (the largest's, as ``ProgramShapes.as_text``
    # answers: the smaller's names only where the largest lacks them) reads
    # the 128-row execution's fusion.1 as the MLP's and its copy as
    # attention's
    merged = {**MAPS["ragged_forward@128"], **MAPS["ragged_forward@512"]}
    one = hand_made(monkeypatch, {**NAMES, "ragged_forward": {
        512: "ragged_forward@512"}}, {**MAPS, "ragged_forward@512": merged})
    wrong = F.split(one)["owners"]
    assert wrong["mlp"] == pytest.approx(0.016 + 0.006)
    assert wrong["attn"] == pytest.approx(0.010 - 0.006 + 0.002)
    assert "other" not in wrong


def test_the_modules_name_carries_a_shape_to_executions_no_record_covers(
        hand):
    """A second execution of the 128-row shape that no traced round's record
    covers (the window cut its round) is read by the map its module's name
    was given by the records of the others."""
    tr = hand["trace"]
    tr["devices"][PLANE]["modules"].append(["jit_ragged_forward(11)", 1.35,
                                            0.006])
    tr["devices"][PLANE]["ops"].append([op("fusion.1"), 1.35, 0.006])
    table = F.split(hand)
    assert table["owners"]["attn"] == pytest.approx(0.010 + 0.006)
    assert table["owners"]["unmapped"] == pytest.approx(0.003)
    # ... and a shape that NO record names is nobody's: unmapped, not a guess
    hand.pop("fwd_split")
    tr["devices"][PLANE]["modules"].append(["jit_ragged_forward(44)", 1.37,
                                            0.004])
    tr["devices"][PLANE]["ops"].append([op("fusion.1"), 1.37, 0.004])
    assert F.split(hand)["owners"]["unmapped"] == pytest.approx(0.003 + 0.004)


@pytest.mark.parametrize("what", ["no_trace", "no_engine", "no_map",
                                  "no_region", "the_parents_engine"])
def test_nothing_to_read_gives_none(hand, monkeypatch, what):
    if what == "no_trace":
        hand["trace"] = None
    elif what == "no_engine":
        hand["engine"] = None
    elif what == "no_map":
        hand["engine"] = Engine({})
    elif what == "no_region":       # a program from before the regions
        for name, opmap in MAPS.items():
            monkeypatch.setitem(mfu._PUBLISHED, name, {
                k: {**e, "region": "other"} for k, e in opmap.items()})
    else:                           # ... and from before the maps
        hand["engine"] = object()
    assert F.split(hand) is None
    for name in SIX:
        assert read(name, hand) is None


def test_the_sweep_is_the_training_splits_own(hand):
    """``sweep`` hands ``train_step_split_ms.split_step`` its operations
    under one name an owner: on the recorded training step the two give the
    same seconds to the same (region, pass)."""
    data = json.loads((Path(__file__).parent / "data" /
                       "train_1chip_v5e.json").read_text())
    tr, opmap = data["trace"], data["opmap"]
    lo, hi = trace.window_of(tr)
    step = train_step_split_ms.steps_of(tr, PLANE, lo, hi)[0]
    theirs = train_step_split_ms.split_step(step, opmap)
    owned = [(train_step_split_ms.owner_of(text, opmap), start, dur)
             for text, start, dur in step
             if trace.op_kind(text) != "collective"]
    ours = F.sweep(owned, lo, hi)
    assert ours.keys() == {k for k in theirs if k[0] != "collective"}
    for key, seconds in ours.items():
        assert seconds == pytest.approx(theirs[key], rel=1e-9)


# ------------------------------------------------------------ recorded
@pytest.fixture(scope="module")
def recorded():
    return json.loads((Path(__file__).parent / "data" /
                       "fwd_split_v5e.json").read_text())


@pytest.fixture
def served(recorded, monkeypatch):
    monkeypatch.setattr(scopes, "compiled_programs", lambda obs: {})
    for name, opmap in recorded["maps"].items():
        monkeypatch.setitem(mfu._PUBLISHED, name, opmap)
    names = {}
    for name in recorded["maps"]:
        program, rows = name.split("@")
        names.setdefault(program, {})[int(rows)] = name
    return {"trace": recorded["trace"],
            "trace_window": tuple(recorded["trace_window"]),
            "rounds": [tuple(r) for r in recorded["rounds"]],
            "stages": recorded["stages"], "engine": Engine(names)}


def test_on_the_recorded_tail_the_owners_resum_and_nothing_is_unmapped(
        served, recorded):
    table = F.split(served)
    tr = served["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, PLANE), *served["trace_window"])
    assert table["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert table["owners"].get("unmapped", 0.0) == 0.0
    # two shapes of ragged_forward ran, each under a module name of its own
    names = trace.program_names(tr, PLANE)
    ragged = {m[0] for m in tr["devices"][PLANE]["modules"]
              if names[m[0]] == "ragged_forward"}
    assert len(ragged) == 2 == len(served["engine"].names["ragged_forward"])
    # every region the model opens owns time, and so does the sampler
    for owner in ("embed", "attn", "mlp", "head", "sample_rows"):
        assert table["owners"][owner] > 0, owner
    expect = recorded["expect"]
    for owner, seconds in expect["owners"].items():
        assert table["owners"][owner] == pytest.approx(seconds, rel=1e-6)
    assert read("mlp_share_pct", served) == pytest.approx(
        expect["mlp_share_pct"], rel=1e-6)
    assert read("fwd_other_share_pct", served) == pytest.approx(
        expect["fwd_other_share_pct"], rel=1e-6)
    assert read("fwd_move_share_pct", served) == pytest.approx(
        expect["fwd_move_share_pct"], rel=1e-6)
    # the attention's row holds at least the paged kernels' time, the
    # MLP's the grouped GEMMs': a Pallas call keeps the region it was
    # traced under
    def kernels(*stems):
        return trace.union_s(
            [e for e in trace.leaf_ops(tr, PLANE)
             if trace.op_kind(e[0]) == "kernel"
             and trace.op_name(e[0]).startswith(stems)],
            *served["trace_window"])
    assert table["owners"]["attn"] >= kernels("paged_", "ragged_") > 0
    assert table["owners"]["mlp"] >= kernels("grouped_") > 0
    assert table["roots"]["attn"]["custom-call"] == pytest.approx(
        kernels("paged_", "ragged_"), rel=1e-6)


def test_on_the_recorded_tail_one_map_for_both_shapes_reads_another_split(
        served, recorded):
    """The two shapes' texts give some names to different regions: read by
    the largest shape's map alone, the smaller shape's executions move time
    between owners (what ``benchmark/scopes.py``'s one text still does)."""
    right = F.split(served)["owners"]
    small, large = sorted(served["engine"].names["ragged_forward"])
    maps = recorded["maps"]
    collide = [k for k, e in maps[f"ragged_forward@{small}"].items()
               if k in maps[f"ragged_forward@{large}"]
               and maps[f"ragged_forward@{large}"][k]["region"]
               != e["region"]]
    assert collide
    served.pop("fwd_split")
    served["engine"] = Engine({**served["engine"].names, "ragged_forward": {
        large: f"ragged_forward@{large}"}})
    wrong = F.split(served)["owners"]
    assert wrong != pytest.approx(right)


# ------------------------------------------------------------ the entries
@pytest.mark.parametrize("name", sorted(SIX))
def test_the_entry_is_listed_as_the_issue_says_and_its_alias_resolves(name):
    args, moves, cells = SIX[name]
    e = BENCH._entry("per_layer", name)
    assert e == {"name": name, "unit": "%", "better": "lower",
                 "source": "device_trace", "layer": "model step",
                 "moves": moves, "workloads": cells}
    assert BENCH.resolved(name) == ("fwd_split_pct", args)
    assert callable(BENCH.reader(name))


def test_the_six_stand_at_the_end_of_the_list_and_the_benchmark_is_sound():
    names = [m["name"] for m in DOC["per_layer"]]
    assert names[-6:] == ["mlp_share_pct", "mlp_share_pct.p95",
                          "fwd_other_share_pct", "fwd_other_share_pct.p95",
                          "fwd_move_share_pct", "fwd_move_share_pct.p95"]
    assert len(names) == 118
    assert BENCH.problems() == []
    # the cells that report serve_tok_s and itl_p95_ms, and no other
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert e2e["serve_tok_s"]["workloads"] == EIGHT
    assert e2e["itl_p95_ms"]["workloads"] == FIVE
