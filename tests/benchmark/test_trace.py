"""The trace reduction: on a hand-made trace where every number can be
counted on fingers, and on a small trace recorded on the v5e (a few rounds
of ``phi2-decode-sat``, kept as JSON in ``data/``)."""
import json
from pathlib import Path

import pytest

from benchmark import trace

KERNEL = ('%closed_call.10 = bf16[32,1,32,128]{3,2,1,0} custom-call(s32[32] '
          '%a), custom_call_target="tpu_custom_call", operand_layout={}')
WHILE = "%while.2 = (s32[], bf16[32,2560]) while((s32[]) %t), body=%b"
FUSION = "%fusion.125 = bf16[32,2560]{1,0} fusion(bf16[32] %x), kind=kLoop"
COPY = "%copy.22 = bf16[32,9600,32,128]{3,2,1,0} copy(bf16[32] %y)"
GATHER = "%all-gather-done.3 = bf16[4096,14336] all-gather-done(%s)"

HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__unknown(111)", 1.0, 0.5],
                    ["jit_dynamic_slice(7)", 1.6, 0.01],
                    ["jit__unknown(222)", 2.0, 0.8],
                    ["jit__unknown(111)", 3.0, 0.5]],
        "ops": [[WHILE, 1.0, 0.5], [FUSION, 1.0, 0.2], [KERNEL, 1.2, 0.1],
                [COPY, 1.3, 0.2], ["%slice.1 = f32[4] slice(%z)", 1.6, 0.01],
                [WHILE, 2.0, 0.8], [FUSION, 2.0, 0.4], [KERNEL, 2.4, 0.4],
                [WHILE, 3.0, 0.5], [FUSION, 3.0, 0.5]]}},
    "host": [["bench/window", 0.5, 3.5],
             ["bench/serve_step", 0.6, 1.2],
             ["PjitFunction(decode_forward)", 0.9, 0.02],
             ["PJRT_LoadedExecutable_Execute", 0.91, 0.001],
             ["PjitFunction(dynamic_slice)", 0.95, 0.01],
             ["PJRT_LoadedExecutable_Execute", 0.951, 0.001],
             ["bench/idle_no_request", 1.8, 0.1],
             ["bench/serve_step", 1.9, 1.0],
             ["PjitFunction(ragged_forward)", 1.95, 0.02],
             ["PJRT_LoadedExecutable_Execute", 1.96, 0.001],
             ["bench/serve_step", 2.9, 0.7],
             ["PjitFunction(decode_forward)", 2.95, 0.02],
             ["PJRT_LoadedExecutable_Execute", 2.96, 0.001]]}
PLANE = "/device:TPU:0"


def test_op_names_and_kinds():
    assert trace.op_name(FUSION) == "fusion.125"
    assert trace.op_kind(KERNEL) == "kernel"
    assert trace.op_kind(WHILE) == "container"
    assert trace.op_kind(COPY) == "copy"
    assert trace.op_kind(FUSION) == "fusion"
    assert trace.op_kind(GATHER) == "collective"
    assert trace.op_kind("%convolution.3 = f32[2] convolution(%a)") == "op"


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([(0, 1), (2, 1)], None, None, 2),           # disjoint
    ([(0, 2), (1, 2)], None, None, 3),           # overlapping
    ([(0, 5), (1, 1)], None, None, 5),           # nested
    ([(0, 5)], 1, 3, 2),                         # clipped both ends
    ([(0, 1), (4, 1)], 2, 3, 0),                 # wholly outside
    ([], None, None, 0),
])
def test_union_of_intervals(intervals, lo, hi, want):
    assert trace.union_s(intervals, lo, hi) == pytest.approx(want)


def test_busy_time_is_a_union_that_skips_loop_containers():
    assert trace.window_of(HAND) == (0.5, 4.0)
    # 1.0-1.5 (fusion, kernel, copy back to back), the slice, 2.0-2.8, 3.0-3.5
    assert trace.busy_s(HAND, 0.5, 4.0) == pytest.approx(
        0.5 + 0.01 + 0.8 + 0.5)
    assert trace.busy_s(HAND, 2.0, 2.4) == pytest.approx(0.4)


def test_unnamed_programs_take_the_name_of_the_span_that_launched_them():
    names = trace.program_names(HAND, PLANE)
    assert names == {"jit__unknown(111)": "decode_forward",
                     "jit__unknown(222)": "ragged_forward",
                     "jit_dynamic_slice(7)": "dynamic_slice"}
    assert trace.program_times(HAND, PLANE, "decode_forward") == [0.5, 0.5]
    assert trace.program_times(HAND, PLANE, "ragged_forward") == [0.8]


def test_without_a_launch_for_every_execution_names_stay_as_they_are():
    cut = {**HAND, "host": HAND["host"][:-1]}
    assert trace.program_names(cut, PLANE)["jit__unknown(111)"] == "unknown"


def test_top_ops_are_named_by_program_kind_and_op():
    top = dict(trace.top_ops(HAND, PLANE, n=3))
    assert top["decode_forward/fusion:fusion.125"] == pytest.approx(0.7)
    assert top["ragged_forward/kernel:closed_call.10"] == pytest.approx(0.4)
    assert top["ragged_forward/fusion:fusion.125"] == pytest.approx(0.4)
    assert len(top) == 3


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(trace.idle_gaps(HAND, PLANE, 0.5, 4.0))
    # the gap's middle decides: 0.5-1.0 and 1.5-1.6 fall in the first step,
    # 1.61-2.0 (middle 1.805) in the wait for a request, 2.8-3.0 (middle
    # 2.9) in the third step, and 3.5-4.0 in no span at all
    assert sum(gaps.values()) == pytest.approx(3.5 - 1.81)
    assert gaps["idle_no_request"] == pytest.approx(0.39)
    assert gaps["serve_step"] == pytest.approx(0.5 + 0.1 + 0.2)
    assert gaps["unattributed"] == pytest.approx(0.5)


def test_exposed_collective_time_is_what_no_other_op_covers():
    t = {"devices": {PLANE: {"modules": [], "ops": [
        [FUSION, 0.0, 1.0], [GATHER, 0.5, 1.0], [FUSION, 2.0, 1.0]]}},
        "host": []}
    assert trace.exposed_s(t, PLANE, 0.0, 3.0) == pytest.approx(0.5)


# ------------------------------------------------ the recorded v5e trace
RECORDED = Path(__file__).parent / "data" / "decode_sat_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_trace_reduces_to_what_the_chip_run_printed(recorded):
    tr, want = recorded["trace"], recorded["expect"]
    lo, hi = trace.window_of(tr)
    plane = sorted(tr["devices"])[0]
    assert hi - lo == pytest.approx(want["window_s"])
    assert trace.busy_s(tr, lo, hi) == pytest.approx(want["busy_s"])
    times = trace.program_times(tr, plane, "decode_forward")
    assert len(times) == want["decode_forward_runs"]
    assert sorted(times)[len(times) // 2] == pytest.approx(
        want["decode_forward_median_s"])
    gaps = dict(trace.idle_gaps(tr, plane, lo, hi))
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo) - trace.busy_s(tr, lo, hi))
    assert max(gaps, key=gaps.get) == "serve_step"


def test_recorded_trace_names_its_programs_and_kernels(recorded):
    tr = recorded["trace"]
    plane = sorted(tr["devices"])[0]
    names = set(trace.program_names(tr, plane).values())
    assert "decode_forward" in names and "unknown" not in names
    top = [name for name, _s in trace.top_ops(tr, plane, n=10)]
    assert all(n.split("/")[0] in names for n in top)
    assert any("/kernel:" in n for n in top)
    kinds = {trace.op_kind(e[0]) for e in tr["devices"][plane]["ops"]}
    assert {"container", "kernel", "fusion", "copy"} <= kinds
