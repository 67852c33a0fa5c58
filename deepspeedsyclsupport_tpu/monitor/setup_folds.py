"""Folds of the set-up ledger's records (``monitor/telemetry.py`` keeps the
records; this file only reads lists of them).

Stdlib only and free of sibling imports, like ``pod.py`` / ``mfu.py`` /
``reqtrace.py``: ``tools/trace_report.py --setup`` loads THIS file by path on
a node without jax, and ``telemetry.setup_summary`` calls the same
functions, so the live process and the offline report fold alike.

Three kinds of record, all on ``time.perf_counter``:

* ``{"kind": "compile", "t", "program", "phase", "dur", "thread"[,
  "cached"]}`` — one per ``/jax/core/compile/*`` event; ``phase`` is
  ``trace`` | ``lower`` | ``compile``, ``t`` the instant the phase ENDED (it
  ran over ``[t - dur, t]``), ``cached`` (``compile`` only) whether the
  persistent cache answered.
* ``{"kind": "span", "id", "name", "t0", "t1", "parent", "thread",
  "fields"}`` — a set-up span; ``parent`` is the enclosing span's ``id``,
  ``t1`` None while it is open.
* ``{"kind": "decision", "t", "name", ...}`` — something the engine decided
  while it was built (``remat``, ``shapes``).

jax reports the phases NESTED: tracing a program traces every inner ``jit``
it calls (each its own ``trace`` event, inside the outer one's interval), a
lowering rule may trace a helper, and an eager operation met while tracing
compiles on the spot. Adding durations up would count such seconds twice, so
every fold here works on SELF seconds (a record's duration less the records
nested in it, :func:`self_seconds`), which add up to wall-clock, and bills
them to the outermost record's program: the program somebody asked for.
"""
import re
from typing import Any, Dict, Iterable, List, Optional

PHASES = ("trace", "lower", "compile")
#: clock slack when deciding that one record's interval lies inside
#: another's: ``dur`` is jax's own ``time.time()`` difference, ``t`` the
#: listener's ``perf_counter`` stamp a few microseconds after the phase ended
NEST_SLACK_S = 2e-4
_WRAPPED = re.compile(r"^(?:jit|pmap)\((.*)\)$")
#: span names whose subtree is an engine being built / its first executions
ENGINE_SPANS = ("engine",)
WARM_SPANS = ("warmup", "first_step")


def program_name(fun_name: Any) -> str:
    """``jit(ragged_forward)`` (what jax calls the module it lowers and
    compiles) and ``ragged_forward`` (what it calls the function it traces)
    are one program."""
    name = str(fun_name)
    m = _WRAPPED.match(name)
    return m.group(1) if m else name


def _end(rec: Dict[str, Any]) -> Optional[float]:
    """The instant a record counts from: a span's ``t1``, else ``t``."""
    return rec.get("t1") if rec.get("kind") == "span" else rec.get("t")


def self_seconds(records: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The ``compile`` records of ``records`` (in the ledger's order: by
    end time), each as ``{**record, "self": seconds, "root": program}``:
    ``self`` is the record's duration less the records nested in it on its
    own thread, ``root`` the program of the outermost record around it."""
    out: List[Dict[str, Any]] = []
    open_by_thread: Dict[Any, List[int]] = {}
    parent: Dict[int, int] = {}
    for rec in records:
        if rec.get("kind") != "compile":
            continue
        i = len(out)
        start = rec["t"] - rec["dur"]
        own = rec["dur"]
        stack = open_by_thread.setdefault(rec.get("thread"), [])
        # whatever ended after this record began (and is claimed by nobody
        # yet) ran inside it; earlier siblings began before it and stay
        while stack and (out[stack[-1]]["t"] - out[stack[-1]]["dur"]
                         >= start - NEST_SLACK_S):
            child = stack.pop()
            parent[child] = i
            own -= out[child]["dur"]
        out.append({**rec, "self": max(0.0, own)})
        stack.append(i)
    for i, rec in enumerate(out):
        root = i
        while root in parent:
            root = parent[root]
        rec["root"] = program_name(out[root]["program"])
    return out


def _inside(rec: Dict[str, Any], span: Dict[str, Any]) -> bool:
    return (rec.get("thread") == span.get("thread")
            and rec["t"] - rec["dur"] >= span["t0"] - NEST_SLACK_S
            and rec["t"] <= span["t1"] + NEST_SLACK_S)


def span_rows(records: Iterable[Dict[str, Any]],
              selfs: Optional[List[Dict[str, Any]]] = None
              ) -> List[Dict[str, Any]]:
    """The closed spans in order of ``t0``, each with ``dur``, ``depth``,
    ``compile_s`` (self seconds of the compile records inside it and inside
    no child of it) and ``self_s`` (its length less its children's and less
    ``compile_s``). ``selfs``: :func:`self_seconds` of the same records,
    where the caller has it already."""
    records = list(records)
    spans = [dict(r) for r in records if r.get("kind") == "span"
             and r.get("t1") is not None]
    spans.sort(key=lambda s: s["t0"])
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        s["dur"] = s["t1"] - s["t0"]
        s["children_s"] = s["compile_s"] = 0.0
        s["depth"] = 0
    for s in spans:
        up = by_id.get(s.get("parent"))
        if up is not None:
            up["children_s"] += s["dur"]
            s["depth"] = up["depth"] + 1
    for rec in self_seconds(records) if selfs is None else selfs:
        # the innermost span around the record: the latest-started one
        home = next((s for s in reversed(spans) if _inside(rec, s)), None)
        if home is not None:
            home["compile_s"] += rec["self"]
    for s in spans:
        s["self_s"] = max(0.0, s["dur"] - s["children_s"] - s["compile_s"])
    return spans


def _no_programs() -> Dict[str, Any]:
    return {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "executables": 0, "cache_misses": 0}


def summarize(records: Iterable[Dict[str, Any]],
              until: Optional[float] = None,
              dropped: int = 0) -> Dict[str, Any]:
    """The ledger folded: what the readers, the report and the tests ask.

    Only records that ended before ``until`` count (None: all). Returns
    ``programs`` (``{program: {"trace_s", "lower_s", "compile_s",
    "executables", "cache_misses"}}``, self seconds billed to the outermost
    program), the same five summed (``trace_s`` ... ``cache_misses``),
    ``engine_s`` / ``warm_run_s`` (the ``engine`` and the ``warmup`` /
    ``first_step`` spans with everything under them, less the compile
    records inside: what building and first running cost beside tracing,
    lowering and compiling), ``spans`` (:func:`span_rows`), ``decisions``
    and ``dropped`` (records the ring let go: a fold over a ledger that
    dropped any is a fold of its tail)."""
    records = [r for r in records
               if until is None or (_end(r) is not None and _end(r) < until)]
    programs: Dict[str, Dict[str, Any]] = {}
    totals = _no_programs()
    selfs = self_seconds(records)
    for rec in selfs:
        row = programs.setdefault(rec["root"], _no_programs())
        for into in (row, totals):
            into[rec["phase"] + "_s"] += rec["self"]
            if rec["phase"] == "compile":
                into["executables"] += 1
                into["cache_misses"] += not rec.get("cached", False)
    spans = span_rows(records, selfs)
    by_id = {s["id"]: s for s in spans}

    def under(s, names):
        while s is not None:
            if s["name"] in names:
                return True
            s = by_id.get(s.get("parent"))
        return False

    return {
        **totals, "programs": programs, "spans": spans,
        "engine_s": sum(s["self_s"] for s in spans
                        if under(s, ENGINE_SPANS)),
        "warm_run_s": sum(s["self_s"] for s in spans
                          if under(s, WARM_SPANS)),
        "decisions": [r for r in records if r.get("kind") == "decision"],
        "dropped": int(dropped)}


def _fmt(sec: float) -> str:
    return f"{sec * 1e3:.1f}ms" if sec < 1.0 else f"{sec:.2f}s"


def render(records: Iterable[Dict[str, Any]], dropped: int = 0,
           top: int = 25) -> List[str]:
    """The operator's reading of a cold start: one line a program (the
    ``top`` costliest), then the span tree with self times, then the
    decisions."""
    s = summarize(records, dropped=dropped)
    lines = [
        "set-up ledger",
        f"  tracing {_fmt(s['trace_s'])}, lowering {_fmt(s['lower_s'])}, "
        f"compile or cache load {_fmt(s['compile_s'])}; "
        f"{s['executables']} executables, {s['cache_misses']} built "
        f"(persistent-cache misses)"
        + (f"; {s['dropped']} older records dropped by the ring"
           if s["dropped"] else ""),
        f"  {'program':<40}{'trace':>10}{'lower':>10}{'compile|load':>14}"
        f"{'execs':>7}{'built':>7}"]
    rows = sorted(s["programs"].items(), key=lambda kv: -(
        kv[1]["trace_s"] + kv[1]["lower_s"] + kv[1]["compile_s"]))
    for name, p in rows[:top]:
        lines.append(
            f"  {name[:39]:<40}{_fmt(p['trace_s']):>10}"
            f"{_fmt(p['lower_s']):>10}{_fmt(p['compile_s']):>14}"
            f"{p['executables']:>7}{p['cache_misses']:>7}")
    if len(rows) > top:
        rest = rows[top:]
        lines.append(
            f"  {'(' + str(len(rest)) + ' more)':<40}"
            f"{_fmt(sum(p['trace_s'] for _, p in rest)):>10}"
            f"{_fmt(sum(p['lower_s'] for _, p in rest)):>10}"
            f"{_fmt(sum(p['compile_s'] for _, p in rest)):>14}"
            f"{sum(p['executables'] for _, p in rest):>7}"
            f"{sum(p['cache_misses'] for _, p in rest):>7}")
    lines.append("  spans (length, self = length less children and the "
                 "compile records inside)")
    for sp in s["spans"]:
        fields = sp.get("fields") or {}
        note = " ".join(f"{k}={v}" for k, v in sorted(fields.items()))
        lines.append(
            f"    {'  ' * sp['depth']}{sp['name']:<{36 - 2 * sp['depth']}}"
            f"{_fmt(sp['dur']):>10}  self {_fmt(sp['self_s']):>9}  "
            f"compiling {_fmt(sp['compile_s']):>9}  {note}".rstrip())
    if not s["spans"]:
        lines.append("    (no set-up span recorded)")
    for d in s["decisions"]:
        what = " ".join(f"{k}={v}" for k, v in sorted(d.items())
                        if k not in ("kind", "t", "name", "thread"))
        lines.append(f"  decision {d.get('name')}: {what}")
    return lines
