"""The training step's device time by region and by pass
(``benchmark/metrics/train_step_split_ms.py`` and its eight aliases, with
``train_step_ms``): on a hand-made trace where every number can be counted on
fingers, and on two whole steps of ``mistral7b-train-1chip`` recorded on the
v5e with the map of the instructions that occur (``data/train_1chip_v5e.json``:
op texts cut to 150 characters, a kernel's to its result type and its mark)."""
import json
from pathlib import Path

import pytest

from benchmark import spec, trace
from deepspeedsyclsupport_tpu.monitor import mfu

BENCH = spec.Bench()
PLANE = "/device:TPU:0"
SPLIT = ("train_attn_ms", "train_mlp_ms", "train_vocab_ms",
         "train_optimizer_ms", "train_other_ms", "train_fwd_ms",
         "train_bwd_ms", "train_recompute_ms")
NINE = ("train_step_ms",) + SPLIT
TRAINING_CELLS = ["mistral7b-train-1chip", "mistral7b-zero3-4chip"]


def publish(monkeypatch, opmap):
    """What ``engine.compiled_train_step()`` + the first ask leave behind."""
    monkeypatch.setitem(mfu._PUBLISHED, "train_batch_fn", opmap)


def read(name, obs):
    return BENCH.reader(name)(obs)


# ------------------------------------------------------------ by hand
def op(name, kind="fusion", mark=""):
    return f"%{name} = bf16[8,128]{{1,0}} {kind}(bf16[8] %x){mark}"


KERNEL_MARK = ", " + trace.KERNEL_MARK
OPMAP = {
    "fusion.1": {"region": "embed", "pass": "fwd"},
    "fusion.2": {"region": "attn", "pass": "fwd"},
    "flash.3": {"region": "attn", "pass": "bwd"},
    "fusion.4": {"region": "mlp", "pass": "recompute"},
    "fusion.5": {"region": "head", "pass": "bwd"},
    "fusion.6": {"region": "optimizer", "pass": None},
    "fusion.7": {"region": "other", "pass": "bwd"},   # a norm's backward
    "copy-start.8": {"region": "other", "pass": None},
    "while.9": {"region": "other", "pass": None},
}


def step_ops(t, slow=0.0):
    """One step from ``t``: 12 ms (+ ``slow`` in the MLP), 11 of them busy."""
    return [
        [op("while.9", "while"), t, 0.012 + slow],      # a container: skipped
        [op("fusion.1"), t, 0.001],
        [op("fusion.2"), t + 0.001, 0.001],
        [op("flash.3", "custom-call", KERNEL_MARK), t + 0.002, 0.002],
        [op("fusion.4"), t + 0.004, 0.002 + slow],
        [op("copy-start.8", "copy-start"), t + 0.0045, 0.0],   # an instant
        # a gather that the MLP hides for 1 ms and that runs alone for 1
        # (the slower MLP hides all of it)
        [op("all-gather-start.1", "all-gather-start"), t + 0.005, 0.002],
        [op("fusion.5"), t + 0.007 + slow, 0.001],
        [op("fusion.6"), t + 0.008 + slow, 0.001],
        # idle for a millisecond
        [op("fusion.7"), t + 0.010 + slow, 0.001],
        [op("fusion.77"), t + 0.011 + slow, 0.001],     # not in the map
    ]


HAND = {
    "devices": {PLANE: {
        "modules": [["jit_train_batch_fn(5)", 0.990, 0.012],   # starts early
                    ["jit_train_batch_fn(5)", 1.010, 0.012],
                    ["jit_convert_element_type(9)", 1.025, 0.001],
                    ["jit_train_batch_fn(5)", 1.030, 0.013],
                    ["jit_train_batch_fn(5)", 1.050, 0.012],
                    ["jit_train_batch_fn(5)", 1.095, 0.012]],  # ends late
        "ops": step_ops(0.990) + step_ops(1.010) + step_ops(1.030, 0.001)
        + step_ops(1.050) + step_ops(1.095)
        + [[op("fusion.2"), 1.025, 0.001]]}},    # another program's op
    "host": [["bench/window", 1.0, 0.1]]}


def hand_obs():
    return {"trace": HAND, "trace_window": trace.window_of(HAND)}


def test_only_steps_wholly_inside_the_window_count():
    reader = BENCH._module("metrics", "train_step_split_ms")
    steps = reader.steps_of(HAND, PLANE, 1.0, 1.1)
    assert [round(min(s for _t, s, _d in ops), 3) for ops in steps] == [
        1.010, 1.030, 1.050]
    assert [len(ops) for ops in steps] == [10, 10, 10]   # no container


def test_every_instant_of_a_step_has_one_owner(monkeypatch):
    publish(monkeypatch, OPMAP)
    reader = BENCH._module("metrics", "train_step_split_ms")
    first, slow, _ = reader.split(hand_obs())
    ms = {("embed", "fwd"): 1, ("attn", "fwd"): 1, ("attn", "bwd"): 2,
          ("mlp", "recompute"): 2, ("collective", None): 1,
          ("head", "bwd"): 1, ("optimizer", None): 1, ("other", "bwd"): 1,
          ("unmapped", None): 1}
    assert dict(first) == {k: pytest.approx(1e-3 * v) for k, v in ms.items()}
    del ms[("collective", None)]        # hidden: it costs nobody anything
    ms[("mlp", "recompute")] = 3
    assert dict(slow) == {k: pytest.approx(1e-3 * v) for k, v in ms.items()}
    for step, ops in zip((first, slow), reader.steps_of(
            HAND, PLANE, 1.0, 1.1)):
        assert sum(step.values()) == pytest.approx(trace.union_s(ops))
        assert sum(step.values()) == pytest.approx(0.011)


@pytest.mark.parametrize("name,want_ms", [
    ("train_step_ms", 12.0),        # median of ALL five executions' time
    ("train_attn_ms", 3.0),         # the projection and the kernel
    ("train_mlp_ms", 2.0),          # 2, 3, 2 over the window's steps
    ("train_vocab_ms", 2.0),        # embed + head (+ loss: none here)
    ("train_optimizer_ms", 1.0),
    ("train_other_ms", 1.0),        # unscoped; NOT the unmapped millisecond
    ("train_fwd_ms", 2.0),          # embed + the attention projection
    ("train_bwd_ms", 3.0),          # kernel + head; not the norm's (other)
    ("train_recompute_ms", 2.0),
])
def test_the_nine_entries_on_the_hand_made_trace(monkeypatch, name, want_ms):
    publish(monkeypatch, OPMAP)
    assert read(name, hand_obs()) == pytest.approx(want_ms)


def test_nothing_to_read_gives_none_and_does_not_raise(monkeypatch):
    # a program that published no step: --trace 1 on a commit before this one
    monkeypatch.delitem(mfu._PUBLISHED, "train_batch_fn", raising=False)
    assert [read(n, hand_obs()) for n in SPLIT] == [None] * 8
    # ... or whose monitor/mfu.py has no ``published`` at all (the parent's)
    monkeypatch.delattr(mfu, "published")
    assert [read(n, hand_obs()) for n in SPLIT] == [None] * 8
    monkeypatch.undo()
    # no trace (an untraced run never asks, but the reader must not care)
    publish(monkeypatch, OPMAP)
    assert [read(n, {"trace": None}) for n in NINE] == [None] * 9
    # a trace in which the program never ran
    other = {"devices": {PLANE: {"modules": [["jit_f(1)", 1.0, 0.01]],
                                 "ops": [[op("fusion.2"), 1.0, 0.01]]}},
             "host": [["bench/window", 0.5, 1.0]]}
    obs = {"trace": other, "trace_window": (0.5, 1.5)}
    assert [read(n, obs) for n in NINE] == [None] * 9


# ------------------------------------------------ the recorded v5e trace
RECORDED = Path(__file__).parent / "data" / "train_1chip_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


@pytest.fixture
def recorded_obs(recorded, monkeypatch):
    publish(monkeypatch, recorded["opmap"])
    tr = recorded["trace"]
    return {"trace": tr, "trace_window": trace.window_of(tr)}


def test_recorded_file_is_small_and_holds_two_whole_steps(recorded):
    assert RECORDED.stat().st_size < 600_000
    tr = recorded["trace"]
    assert len(trace.program_times(tr, PLANE, "train_batch_fn")) == 2
    used = {trace.op_name(text)
            for program, text, _s, _d in trace.ops_by_program(tr, PLANE)
            if program == "train_batch_fn"}
    assert used == set(recorded["opmap"])    # the map of what occurs


def test_recorded_regions_and_passes_partition_each_steps_busy_time(
        recorded, recorded_obs):
    reader = BENCH._module("metrics", "train_step_split_ms")
    steps = reader.split(recorded_obs)
    assert len(steps) == recorded["expect"]["steps"] == 2
    for step, busy_ms in zip(steps, recorded["expect"]["busy_ms"]):
        by_region, by_pass = {}, {}
        for (region, pass_), s in step.items():
            by_region[region] = by_region.get(region, 0.0) + 1e3 * s
            if region in reader.MODEL_REGIONS:
                by_pass[pass_] = by_pass.get(pass_, 0.0) + 1e3 * s
        assert set(by_region) <= set(mfu.REGIONS)       # nothing unmapped
        assert set(by_pass) == set(mfu.PASSES)
        rest = sum(by_region.get(r, 0.0)
                   for r in ("optimizer", "other", "collective"))
        assert sum(by_region.values()) == pytest.approx(busy_ms, rel=0.01)
        assert sum(by_pass.values()) + rest == pytest.approx(busy_ms,
                                                             rel=0.01)


@pytest.mark.parametrize("name", SPLIT)
def test_recorded_trace_reads_what_it_read_when_it_was_cut(
        recorded, recorded_obs, name):
    assert read(name, recorded_obs) == pytest.approx(
        recorded["expect"][name])


def test_recorded_sums_and_the_flash_kernels_inside_attention(
        recorded, recorded_obs):
    got = {n: read(n, recorded_obs) for n in NINE}
    busy = sorted(recorded["expect"]["busy_ms"])[0]    # nearest-rank median
    regions = sum(got[n] for n in SPLIT[:5])           # one chip: no gather
    passes = sum(got[n] for n in SPLIT[3:])
    assert regions == pytest.approx(busy, rel=0.01)
    assert passes == pytest.approx(busy, rel=0.01)
    # the step on the device is its busy time and a hundredth of idle
    assert busy <= got["train_step_ms"] <= 1.01 * busy
    # flash_roofline's kernels (custom calls, told apart by result type)
    # lie under mfu.attn, one of them in each pass and two in the backward
    assert got["train_attn_ms"] >= max(recorded["expect"]["kernel_ms"])
    kernels = {trace.op_name(o[0]): recorded["opmap"][trace.op_name(o[0])]
               for o in recorded["trace"]["devices"][PLANE]["ops"]
               if trace.op_kind(o[0]) == "kernel"}
    assert {e["region"] for e in kernels.values()} == {"attn"}
    assert sorted(e["pass"] for e in kernels.values()) == [
        "bwd", "bwd", "fwd", "recompute"]
    flash = BENCH._module("metrics", "flash_roofline")
    texts = {trace.op_name(o[0]): o[0]
             for o in recorded["trace"]["devices"][PLANE]["ops"]}
    assert sorted((kernels[n]["pass"], flash.kernel_of(texts[n]))
                  for n in kernels) == [
        ("bwd", "dkv"), ("bwd", "dq"), ("fwd", "fwd"), ("recompute", "fwd")]


def test_recorded_trace_without_a_map_reads_nothing(recorded, monkeypatch):
    monkeypatch.delitem(mfu._PUBLISHED, "train_batch_fn", raising=False)
    tr = recorded["trace"]
    obs = {"trace": tr, "trace_window": trace.window_of(tr)}
    assert [read(n, obs) for n in SPLIT] == [None] * 8
    assert read("train_step_ms", obs) is not None     # needs no map


# ------------------------------------------------ BENCHMARK.json's entries
@pytest.mark.parametrize("name", NINE)
def test_the_entry_is_the_training_cells_alone(name):
    entry = BENCH._entry("per_layer", name)
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "moves": "train_tok_s",
        "layer": ("model step (train)" if name in SPLIT[:3]
                  else "train engine"),
        "workloads": TRAINING_CELLS}
    alias = spec.load_json(BENCH.root / "benchmark" / "metrics"
                           / (name + ".json"))
    assert alias["reader"] == ("program_ms" if name == "train_step_ms"
                               else "train_step_split_ms")
