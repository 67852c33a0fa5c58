"""The program's own round records, in the window and on the trace's clock.

``ServingSession`` writes one ``round`` stage record per scheduling round
(``obs["stages"]``, drained from its ring): the round's ends ``t0``/``t1``
and the forward's launch ``launch_t`` on the session's clock — the harness
gives it ``time.perf_counter``, its own —, the ``program`` it dispatched,
what that forward covered (``ctx_tokens``, ...) and where the host's time
went (``phases``: seconds by ``reqtrace.ROUND_PHASES``). The readers under
``metrics/`` that take their numbers from those records find them here.

The profiler's trace has a clock of its own. The harness brackets every
round twice with the same instants, ``obs["rounds"]`` on its clock and the
``bench/serve_step`` span in the trace; ``clock_offset`` lays the one over
the other, and ``traced_rounds`` hands each traced round's record over with
its times moved onto the trace's clock, beside the device's events, once the
program's own ``launch_t`` has confirmed the offset against the trace's span
of the forward's launch.

A program that writes no such record (every commit before the one that
added it) gives every function here nothing to read: they return ``None``.
"""
import bisect
import statistics
import sys

from . import trace

ROUND_SPAN = "bench/serve_step"   # the harness's span around session.step()
# what the ``round_*_ms`` metrics sum: the host's work before the forward
# can be planned, planning it, launching it, and everything after the launch
GROUPS = {
    "pre": ("gather", "sample", "readback", "emit"),
    "plan": ("queue", "schedule", "build"),
    "launch": ("dispatch",),
    "post": ("collect", "account", "other"),
}


def say(why):
    print(f"benchmark.spans: no reading: {why}", file=sys.stderr)


def round_records(obs):
    """The ``data`` of every ``round`` record the session kept, in order."""
    return sorted((s["data"] for s in obs.get("stages") or ()
                   if s["name"] == "serve/stage"
                   and s["data"].get("stage") == "round"),
                  key=lambda d: d["t0"])


def records_of(rounds, records):
    """The record of each of the harness's ``rounds`` (``obs["rounds"]``
    entries): the one whose ends lie inside the harness's span of that
    round. ``None`` if any round has none: the session's ring drops its
    oldest records when it is full (``stats()["trace_dropped"]``), and a
    median over the rounds that happen to be left would not be the
    window's."""
    starts = [d["t0"] for d in records]
    out = []
    for h0, h1, *_ in rounds:
        k = bisect.bisect_left(starts, h0)
        if k == len(records) or records[k]["t1"] > h1:
            return None
        out.append(records[k])
    return out


def window_records(obs):
    """One record for every round that ended inside the measured window, or
    ``None`` (said on stderr) where the records do not cover it."""
    t0, t1 = obs["window"]
    rounds = [r for r in obs["rounds"] if t0 < r[1] <= t1]
    records = round_records(obs)
    found = records_of(rounds, records) if rounds and records else None
    if found is None:
        say(f"{len(records)} round records do not cover the window's "
            f"{len(rounds)} rounds (none: the program writes no round "
            f"record; some: the session's ring dropped the oldest, its "
            f"stats() count them as trace_dropped)")
    return found


def clock_offset(rounds, host_events, tol_s=0.5e-3):
    """``(offset, k)``: trace time = harness time + ``offset``, and the
    trace's ``bench/serve_step`` spans are ``rounds[k:k + n]``.

    The traced rounds are a run of consecutive harness rounds, and the two
    bracket the same call: at the right ``k`` every span's start minus its
    round's ``t0`` and every end minus its ``t1`` is the same number to
    within microseconds. Raises ``ValueError`` where no run fits within
    ``tol_s``, or two do."""
    spans = sorted((e for e in host_events if e[0] == ROUND_SPAN),
                   key=lambda e: e[1])
    n = len(spans)
    if not n or n > len(rounds):
        raise ValueError(f"{n} {ROUND_SPAN} spans against {len(rounds)} "
                         f"rounds of the harness")
    fits = []
    for k in range(len(rounds) - n + 1):
        diffs = [x for s, r in zip(spans, rounds[k:k + n])
                 for x in (s[1] - r[0], s[1] + s[2] - r[1])]
        spread = max(diffs) - min(diffs)
        if spread <= tol_s:
            fits.append((spread, k, statistics.median(diffs)))
    if len(fits) != 1:
        raise ValueError(
            f"{len(fits)} runs of {n} consecutive rounds fit the trace's "
            f"{ROUND_SPAN} spans within {tol_s * 1e3} ms (need exactly one)")
    _spread, k, offset = fits[0]
    return offset, k


def launch_skew(rounds, host_events):
    """Median over ``rounds`` (records on the trace's clock) of ``launch_t``
    minus the end of the round's ``PjitFunction(<program>)`` span, the
    host's call of the forward it names; ``None`` where no round launched
    anything. The program reads ``launch_t`` the instant that call returns,
    and the harness's spans had no part in the reading: it checks both the
    offset they gave and that the record names the launch it timed. A
    median over the rounds whose span the trace kept, so that one round in
    which the host was taken off the core between the two does not void the
    run. Raises ``ValueError`` where rounds launched and the trace holds
    the span of none."""
    skews, launched = [], 0
    for d in rounds:
        if not d["program"]:
            continue
        launched += 1
        name = f"PjitFunction({d['program']})"
        ends = [e[1] + e[2] for e in host_events
                if e[0] == name and d["t0"] <= e[1] <= d["t1"]]
        if ends:
            skews.append(d["launch_t"] - max(ends))
    if launched and not skews:
        raise ValueError(f"{launched} rounds say they launched a forward; "
                         f"the trace has no {name} in any of them")
    return statistics.median(skews) if skews else None


def traced_rounds(obs, skew_s=(-1e-4, 2e-3)):
    """The records of the rounds whose ``bench/serve_step`` span lies inside
    the traced window, as copies with ``t0``, ``t1`` and ``launch_t`` on the
    TRACE's clock; ``None`` (said on stderr) without a trace, without
    records for those rounds, or where the clocks cannot be laid over each
    other: no run of rounds fits the spans, or the program's own launch
    instants then miss the trace's (``launch_skew`` outside ``skew_s``)."""
    tr = obs.get("trace")
    if tr is None:
        return None
    try:
        offset, k = clock_offset(obs["rounds"], tr["host"])
    except ValueError as e:
        say(str(e))
        return None
    lo, hi = obs["trace_window"]
    rounds = [r for r in obs["rounds"][k:]
              if lo <= r[0] + offset and r[1] + offset <= hi]
    found = records_of(rounds, round_records(obs)) if rounds else None
    if found is None:
        say(f"no round record for each of the {len(rounds)} traced rounds")
        return None
    found = [{**d, **{key: d[key] + offset for key in ("t0", "t1", "launch_t")
                      if d[key] is not None}} for d in found]
    try:
        skew = launch_skew(found, tr["host"])
    except ValueError as e:
        say(str(e))
        return None
    if skew is not None and not skew_s[0] <= skew <= skew_s[1]:
        say(f"launch_t lies {skew * 1e3:.3f} ms (median) after the end of "
            f"the forward's PjitFunction span, outside {skew_s} s")
        return None
    return found


class Device:
    """The first device's side of a trace, indexed for per-round questions:
    program executions in order, and the merged intervals in which any
    operation ran."""

    def __init__(self, tr):
        self.plane = sorted(tr["devices"])[0]
        names = trace.program_names(tr, self.plane)
        self.runs = sorted((m[1], m[1] + m[2], names[m[0]])
                           for m in tr["devices"][self.plane]["modules"])
        ops = trace.leaf_ops(tr, self.plane)
        self.kernels = sorted((e[1], e[2]) for e in ops
                              if trace.op_kind(e[0]) == "kernel")
        self.busy = []
        for a, b in sorted((e[1], e[1] + e[2]) for e in ops):
            if self.busy and a <= self.busy[-1][1]:
                self.busy[-1][1] = max(self.busy[-1][1], b)
            else:
                self.busy.append([a, b])
        self._ends = [b for _a, b in self.busy]

    def idle_s(self, lo, hi):
        """Seconds of ``[lo, hi]`` in which no operation ran."""
        if hi <= lo:
            return 0.0
        busy = 0.0
        for a, b in self.busy[bisect.bisect_right(self._ends, lo):]:
            if a >= hi:
                break
            busy += min(b, hi) - max(a, lo)
        return hi - lo - busy

    def forward(self, program, lo, hi):
        """``(start, end)`` of the first execution of ``program`` that
        starts inside ``[lo, hi]``, or ``None``."""
        k = bisect.bisect_left(self.runs, (lo,))
        for a, b, name in self.runs[k:]:
            if a > hi:
                break
            if name == program:
                return a, b
        return None

    def kernels_in(self, lo, hi):
        """``(calls, seconds)`` of the Pallas custom calls that start
        inside ``[lo, hi]``."""
        k = bisect.bisect_left(self.kernels, (lo,))
        took = [d for a, d in self.kernels[k:bisect.bisect_right(
            self.kernels, (hi,))]]
        return len(took), sum(took)


def floor_share(obs, ops, program, least_s):
    """100 x the least seconds over the seconds taken, summed over the
    traced rounds that launched ``program``: ``least_s(record)`` is a
    round's floor (``None`` or 0: nothing of it in this round), the seconds
    taken those of ``ops`` (``scopes.scoped_ops``) inside the forward's
    execution. ``None`` where no round gives both."""
    dev = Device(obs["trace"])
    ideal = took = 0.0
    for d in traced_rounds(obs) or ():
        least = least_s(d) if d["program"] == program else None
        ran = least and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran:
            continue
        seconds = sum(dur for _l, launched, start, dur in ops
                      if launched == program and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += least
        took += seconds
    return 100.0 * ideal / took if took else None

