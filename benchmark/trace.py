"""From the profiler's trace to numbers: the benchmark's own reduction.

``read_xplane`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into
plain data — ``{"devices": {plane: {"modules": [...], "ops": [...]}},
"host": [...]}`` with every event a ``[name, start_s, duration_s]`` on the
trace's clock — and everything else here works on that plain data, so the
tests check it on a small recorded trace kept as JSON
(``tests/benchmark/data``).

What a TPU trace looks like (read off a v5e trace, PR 23): one plane per
chip (``/device:TPU:<n>``); its ``XLA Modules`` line has one event per
program execution (``jit_train_batch_fn(<hash>)``; the serving forwards read
``jit__unknown(<hash>)``), its ``XLA Ops`` line one event per HLO
instruction, named by the instruction's full text, with ``while`` loops
present BOTH as one long event and as their bodies' events (so a sum must
skip the containers, and busy time is a union). A Pallas kernel is an op
whose text says ``custom_call_target="tpu_custom_call"``. The host plane
carries the harness's own ``bench/...`` annotations, jax's
``PjitFunction(<name>)`` spans and one ``PJRT_LoadedExecutable_Execute`` per
program launch: launches and device executions are the same sequence, which
is how a ``jit__unknown`` gets its name back.
"""
import bisect
import collections
import functools
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
LAUNCH = "PJRT_LoadedExecutable_Execute"
HOST_KEEP = ("bench/", "PjitFunction(", LAUNCH)
WINDOW_SPAN = "bench/window"   # the harness's span over the traced window


def window_of(trace):
    """``(lo, hi)`` of the traced window on the trace's clock."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    raise ValueError(f"the trace has no {WINDOW_SPAN} span")


def read_xplane(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = out["devices"][plane.name] = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns / 1e9,
                                 e.duration_ns / 1e9] for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [
                    [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                    for e in line.events if e.name.startswith(HOST_KEEP)]
    out["host"].sort(key=lambda e: e[1])
    return out


# ------------------------------------------------------------------ names
def op_name(text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


@functools.lru_cache(maxsize=None)   # a trace repeats a few thousand texts
def op_kind(text):
    name = op_name(text)
    if KERNEL_MARK in text:
        return "kernel"
    stem = re.sub(r"[.\d]+$", "", name)
    if stem in CONTAINERS:
        return "container"
    if any(stem.startswith(c) for c in COLLECTIVES):
        return "collective"
    if stem.startswith("copy") or stem.startswith("slice"):
        return "copy"
    return "fusion" if "fusion" in stem else "op"


def program_names(trace, plane):
    """``{module event name: program}``: ``jit_x(<hash>)`` is ``x``, and a
    ``jit__unknown`` takes the name of the ``PjitFunction(...)`` span its
    launches happened in (k-th launch = k-th execution; needs the traced
    window to start and end with the device drained, else no names)."""
    mods = sorted(trace["devices"][plane]["modules"], key=lambda e: e[1])
    names = {m[0]: re.sub(r"^jit_+|\(\d+\)$", "", m[0]) for m in mods}
    launches = [e for e in trace["host"] if e[0] == LAUNCH]
    if len(launches) != len(mods):
        return names
    pjits = [e for e in trace["host"] if e[0].startswith("PjitFunction(")]
    starts = [p[1] for p in pjits]
    votes = collections.defaultdict(collections.Counter)
    for launch, mod in zip(launches, mods):
        # the innermost span around the launch: the latest-started of the
        # few spans before it that has not ended yet
        k = bisect.bisect_right(starts, launch[1])
        for p in reversed(pjits[max(0, k - 8):k]):
            if launch[1] <= p[1] + p[2]:
                votes[mod[0]][p[0][len("PjitFunction("):-1]] += 1
                break
    for mod, c in votes.items():
        if names[mod] == "unknown":
            names[mod] = c.most_common(1)[0][0]
    return names


# ---------------------------------------------------------------- reductions
def union_s(intervals, lo=None, hi=None):
    """Total length of the union of ``[start, start + dur)`` intervals,
    clipped to ``[lo, hi]``."""
    total, end = 0.0, None
    for s, d in sorted((e[-2], e[-1]) for e in intervals):
        a, b = s, s + d
        if lo is not None:
            a, b = max(a, lo), max(b, lo)
        if hi is not None:
            a, b = min(a, hi), min(b, hi)
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def leaf_ops(trace, plane):
    """Ops that are work themselves (loop and call containers left out)."""
    return [e for e in trace["devices"][plane]["ops"]
            if op_kind(e[0]) != "container"]


def busy_s(trace, lo, hi):
    """Seconds an operation ran on the device inside ``[lo, hi]``,
    averaged over the chips."""
    planes = trace["devices"]
    return sum(union_s(leaf_ops(trace, p), lo, hi) for p in planes) \
        / max(1, len(planes))


def program_times(trace, plane, program):
    """Device seconds of every execution of ``program`` on ``plane``."""
    names = program_names(trace, plane)
    return [m[2] for m in trace["devices"][plane]["modules"]
            if names[m[0]] == program]


def ops_by_program(trace, plane):
    """``(program, op text, start, dur)`` for every leaf op, each put in
    the program execution that contains its start."""
    names = program_names(trace, plane)
    mods = sorted(trace["devices"][plane]["modules"], key=lambda e: e[1])
    ops = sorted(leaf_ops(trace, plane), key=lambda e: e[1])
    out, i = [], 0
    for text, start, dur in ops:
        while i + 1 < len(mods) and mods[i + 1][1] <= start:
            i += 1
        inside = mods and mods[i][1] <= start <= mods[i][1] + mods[i][2]
        out.append((names[mods[i][0]] if inside else "-", text, start, dur))
    return out


def top_ops(trace, plane, n=10):
    """The ``n`` ops that took most device time, as
    ``[["program/kind:op", seconds], ...]``."""
    total = collections.Counter()
    for program, text, _s, dur in ops_by_program(trace, plane):
        total[f"{program}/{op_kind(text)}:{op_name(text)}"] += dur
    return [[k, v] for k, v in total.most_common(n)]


def idle_gaps(trace, plane, lo, hi, n=10):
    """Device-idle seconds inside ``[lo, hi]`` by what the harness's host
    span was doing at the middle of each gap (``bench/<name>`` spans;
    ``unattributed`` where none covers it): ``[[name, seconds], ...]``."""
    busy = sorted((e[1], e[1] + e[2]) for e in leaf_ops(trace, plane))
    spans = [e for e in trace["host"]
             if e[0].startswith("bench/") and e[0] != WINDOW_SPAN]
    starts = [s[1] for s in spans]   # the harness's spans do not nest
    total = collections.Counter()
    cursor = lo
    for a, b in busy + [(hi, hi)]:
        a, b = min(max(a, lo), hi), min(max(b, lo), hi)
        if a > cursor:
            mid = (cursor + a) / 2
            k = bisect.bisect_right(starts, mid) - 1
            covered = k >= 0 and mid <= spans[k][1] + spans[k][2]
            total[spans[k][0][len("bench/"):] if covered
                  else "unattributed"] += a - cursor
        cursor = max(cursor, b)
    return [[k, v] for k, v in total.most_common(n)]


def exposed_s(trace, plane, lo, hi):
    """Seconds inside ``[lo, hi]`` in which a collective ran on ``plane``
    and no other op did: collective time that compute does not hide."""
    ops = leaf_ops(trace, plane)
    rest = [e for e in ops if op_kind(e[0]) != "collective"]
    return union_s(ops, lo, hi) - union_s(rest, lo, hi)
