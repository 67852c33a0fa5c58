"""The serving forwards' device time by MFU region: the share, in per cent of
the device's busy time in the traced window, of the instants that belong to
the asked regions (``args: {"regions": [...]}``: ``embed attn mlp head other
unmapped``) or to operations whose ``root`` only MOVES data, whatever their
region (``args: {"roots": [...]}``: the opcodes that count as moving), inside
the executions of ``decode_forward`` and ``ragged_forward``.

Every instant of the window's busy time on device 0 goes to ONE owner, the
leaf operation running then (the latest started where several run:
``train_step_split_ms.split_step``'s sweep, called here). An operation inside
an execution of one of the two forwards is owned by ``(region, root)`` of its
instruction in the map of ITS execution's shape: the serving model wraps its
phases in ``mfu.region_scope`` as the training model does,
``InferenceEngineV2.compiled_programs()`` hands each compiled ``(program,
rows)`` to ``monitor/mfu.publish`` as ``<program>@<rows>``
(``engine.published_programs()`` has the names), and ``mfu.published`` reads
region and root off every instruction's line (``root``: what a fusion's fused
computation ends in; a Pallas call is a ``custom-call``, never a move). The compiler numbers the shapes' instructions
differently, so one name may sit under two regions in two shapes: an
execution of a program with several shapes takes the map of the ``rows`` its
``round`` record names (``spans.traced_rounds``: the records on the trace's
clock; the record's forward is the first execution of its program inside the
round), and the trace's own name of the module (``jit_ragged_forward(<hash>)``,
one hash a shape) carries that to the executions no record covers. An
operation of any other program (``sample_rows``, ``split_key``) is owned by
that program's name, one outside every execution by ``-``, an instruction its
map does not know by ``unmapped``. So

    embed + attn + mlp + head + other + unmapped + the other programs

is the window's busy time (``split(obs)`` has the whole table, by ``root``
inside each region too; ``tools/bench_unlisted.py`` prints it). ``other`` is
what NO line of the model asked for (a copy, pad or transpose the compiler
placed outside every scope, a loop's bookkeeping): the finding, not a
remainder. A fusion that both moves and computes counts by its root. Shares
and not milliseconds, as every ``*_share_pct`` of the serving list: a share
falls when a neighbour grows.

``None`` without a trace or an engine, and from a program that publishes no
map or opens no region (every commit before the one that added them).
"""
import bisect
import collections
import sys

from benchmark import scopes, spans, trace
from benchmark.metrics import train_step_split_ms

FORWARDS = ("decode_forward", "ragged_forward")
REGIONS = ("embed", "attn", "mlp", "head")     # what the serving model opens
UNMAPPED = "unmapped"


def say(why):
    print(f"benchmark.fwd_split_pct: {why}", file=sys.stderr)


def published_maps(obs):
    """``{program: {rows: {instruction: {"region", "root", ...}}}}`` of the
    two forwards as the engine compiled them (asked through
    ``scopes.compiled_programs``: a run compiles once); ``None`` where the
    program publishes none or opens no region."""
    from deepspeedsyclsupport_tpu.monitor import mfu

    names_of = getattr(obs["engine"], "published_programs", None)
    if names_of is None:
        return None
    scopes.compiled_programs(obs)
    maps = {program: {rows: mfu.published(name)
                      for rows, name in by_rows.items()}
            for program, by_rows in names_of().items()
            if program in FORWARDS}
    opened = any(entry["region"] in REGIONS
                 for by_rows in maps.values() for opmap in by_rows.values()
                 for entry in opmap.values())
    return maps if opened else None


def rows_by_module(tr, plane, maps, rounds):
    """``{module event name: rows}`` for the executions of a forward that
    has several shapes: each traced round's record names the ``rows`` of the
    forward it launched, the first execution of its program inside the
    round, and the trace names a shape's every execution alike. A name that
    the records give two shapes keeps the one most of them say, said on
    stderr."""
    names = trace.program_names(tr, plane)
    runs = sorted((m[1], m[0]) for m in tr["devices"][plane]["modules"])
    votes = collections.defaultdict(collections.Counter)
    for d in rounds or ():
        program = d.get("program")
        if len(maps.get(program, ())) < 2 or d.get("rows") is None:
            continue
        for start, module in runs[bisect.bisect_left(runs, (d["t0"],)):]:
            if start > d["t1"]:
                break
            if names[module] == program:
                votes[module][d["rows"]] += 1
                break
    out = {}
    for module, said in votes.items():
        out[module] = said.most_common(1)[0][0]
        if len(said) > 1:
            say(f"the round records give {module} the rows {dict(said)}")
    return out


def owned_ops(obs, maps):
    """``[((owner, root), start, seconds), ...]`` of device 0's leaf
    operations. Inside a forward ``owner`` is a region and ``root`` its
    instruction's, or ``(unmapped, None)``; inside any other program
    ``(the program's name, None)``; outside every execution ``("-",
    None)``."""
    tr = obs["trace"]
    plane = sorted(tr["devices"])[0]
    names = trace.program_names(tr, plane)
    mods = sorted(tr["devices"][plane]["modules"], key=lambda m: m[1])
    several = any(len(by_rows) > 1 for by_rows in maps.values())
    rows_of = rows_by_module(tr, plane, maps, spans.traced_rounds(obs)) \
        if several else {}

    def map_of(module):
        by_rows = maps.get(names[module])
        if by_rows is None:
            return None                      # not a forward: by its name
        if len(by_rows) == 1:
            return next(iter(by_rows.values()))
        return by_rows.get(rows_of.get(module), {})

    kept = {m[0]: map_of(m[0]) for m in mods}
    out, i = [], 0
    for text, start, dur in sorted(trace.leaf_ops(tr, plane),
                                   key=lambda e: e[1]):
        while i + 1 < len(mods) and mods[i + 1][1] <= start:
            i += 1
        if not (mods and mods[i][1] <= start <= mods[i][1] + mods[i][2]):
            out.append((("-", None), start, dur))
            continue
        opmap = kept[mods[i][0]]
        if opmap is None:
            out.append(((names[mods[i][0]], None), start, dur))
            continue
        entry = opmap.get(trace.op_name(text))
        out.append(((entry["region"], entry["root"]) if entry
                    else (UNMAPPED, None), start, dur))
    return out


def sweep(owned, lo, hi):
    """``{owner: seconds}`` of ``[(owner, start, seconds), ...]`` clipped to
    ``[lo, hi]``: every instant in which something ran, given to the latest
    started. The sweep is ``train_step_split_ms.split_step``'s, which reads
    an operation's owner off its name through a map: each owner goes in
    under a name of its own."""
    key = {}
    ops = []
    for owner, start, dur in owned:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            ops.append((f"o{key.setdefault(owner, len(key))} = ", a, b - a))
    opmap = {f"o{k}": {"region": owner[0], "pass": owner[1]}
             for owner, k in key.items()}
    return train_step_split_ms.split_step(ops, opmap)


def split(obs):
    """The traced window's busy time on device 0 by owner: ``{"busy_s",
    "forwards_s" (inside the two forwards), "owners": {owner: seconds},
    "roots": {region or "unmapped": {root: seconds}}}``; ``None`` without a
    trace, an engine, a published map or a region. Kept on ``obs``: six
    entries read it."""
    if "fwd_split" not in obs:
        obs["fwd_split"] = _split(obs)
    return obs["fwd_split"]


def _split(obs):
    if obs.get("trace") is None or obs.get("engine") is None:
        return None
    maps = published_maps(obs)
    if not maps:
        return None
    owners, roots = collections.Counter(), {}
    for (owner, root), s in sweep(owned_ops(obs, maps),
                                  *obs["trace_window"]).items():
        owners[owner] += s
        if owner in REGIONS + ("other", UNMAPPED):
            roots.setdefault(owner, collections.Counter())[root or "-"] += s
    return {"busy_s": sum(owners.values()),
            "forwards_s": sum(sum(row.values()) for row in roots.values()),
            "owners": dict(owners),
            "roots": {owner: dict(row) for owner, row in roots.items()}}


def read(obs, regions=(), roots=()):
    table = split(obs)
    if not table or not table["busy_s"]:
        return None
    took = sum(s for owner, row in table["roots"].items()
               for root, s in row.items()
               if owner in regions or root in roots)
    return 100.0 * took / table["busy_s"]
