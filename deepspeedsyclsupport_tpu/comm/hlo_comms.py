"""Post-compile accounting of XLA-inserted collectives.

The façade logger (``comms_logging.py``) sees only EXPLICIT collective
calls; under SPMD most traffic — every stage-2/3 all-gather and
reduce-scatter the partitioner inserts — never passes through it. This
module closes that gap (reference: per-op logging in ``comm/comm.py:101``
has the same blind spot for its fused paths, which is why its
``log_summary`` is authoritative there and ours must read the compiled
program): walk the optimized HLO of a compiled step and tally every
collective op's payload bytes.

The parse works on the compiled module text (``Compiled.as_text()``) —
stable, version-robust fields: result shape, opcode, replica_groups.
"""
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional

import numpy as np

# opcodes that move data between devices (start/done pairs counted once)
COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# the result shape is matched lazily up to the opcode: a TPU layout such as
# ``{1,0:T(8,128)(2,1)}`` carries parentheses of its own inside a tuple
_INSTR_RE = re.compile(
    r"=\s*(\(.*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(COLLECTIVE_OPS) + r")(-start|-done)?\(")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*\}(?:,\{[^}]*\})*\}|\[[0-9,]+\]<=\[[0-9,]+\])")


def _parts(shape_text: str) -> List[Dict[str, int]]:
    """``[{bytes, elems}]`` of each array in a shape expression: one entry
    for a plain shape, one per element for the tuple a COMBINED collective
    returns (XLA's combiner passes merge several leaves' syncs into one
    op)."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = int(np.prod([int(d) for d in dims.split(",")])) if dims else 1
        out.append({"bytes": n * _DTYPE_BYTES[dtype], "elems": n,
                    "context": dtype == "u32" and not dims})
    return out


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of a shape expression — 'f32[8,128]{1,0}' or a tuple
    '(bf16[4,2], u32[4])'."""
    return sum(p["bytes"] for p in _parts(shape_text))


_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"\bwhile\(.*\bcondition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALLEE_RE = re.compile(r"\b(?:to_apply|calls|body|condition)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONST_RE = re.compile(r"\bs32\[\][^ ]* constant\((\d+)\)")


def _executions(hlo_text: str) -> Dict[str, int]:
    """How many times one run of the module executes each computation: a
    ``while`` body runs its trip count times (``known_trip_count`` where XLA
    annotates it; else the lone ``s32 constant(N)`` its ``compare LT``
    condition tests — the shape every ``lax.scan`` lowers to), everything
    else once per execution of its caller. A layer scan's per-layer
    collectives sit ONCE in the text and run L times."""
    bodies: Dict[str, List[str]] = defaultdict(list)
    current = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            current = m.group(1)
        elif current is not None:
            bodies[current].append(line)
    calls: Dict[str, List] = defaultdict(list)   # callee -> [(caller, n)]
    for comp, lines in bodies.items():
        for line in lines:
            w = _WHILE_RE.search(line)
            trip = 1
            if w:
                t = _TRIP_RE.search(line)
                if t:
                    trip = int(t.group(1))
                else:
                    cond = "\n".join(bodies.get(w.group(1), ()))
                    consts = _CONST_RE.findall(cond)
                    if len(consts) == 1 and "direction=LT" in cond:
                        trip = int(consts[0])
            for callee in _CALLEE_RE.findall(line):
                calls[callee].append(
                    (comp, trip if w and callee == w.group(2) else 1))

    memo: Dict[str, int] = {}

    def runs(comp: str) -> int:
        if comp not in memo:
            memo[comp] = 1  # guards a (never expected) cycle
            memo[comp] = sum(n * runs(caller)
                             for caller, n in calls[comp]) or 1
        return memo[comp]

    return {comp: runs(comp) for comp in bodies}


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_RE.search(line)
    if not m:
        return None
    text = m.group(1)
    if text.startswith("{{"):
        first = text[2:].split("}", 1)[0]
        return len([t for t in first.split(",") if t.strip()])
    # iota form [N,M]<=[...]: groups of size M
    dims = text[1:].split("]", 1)[0].split(",")
    return int(dims[-1])


def parse_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Every data-moving collective in a compiled HLO module:
    ``{op, bytes, parts, shape, group_size, executions}`` — ``bytes`` is one
    execution's payload, ``parts`` its per-array split (``_parts``) and
    ``executions`` how often a run of the module executes the op."""
    executions = _executions(hlo_text)
    out = []
    current = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION_RE.match(line)
        if c:
            current = c.group(1)
            continue
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_text, opcode, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue  # the -start carries the payload; count pairs once
        parts = _parts(shape_text)
        if phase == "-start" and opcode != "all-reduce" \
                and shape_text.startswith("("):
            # an async start returns (operand(s), output(s)[, u32 context
            # scalars]): only the outputs are payload actually moved —
            # counting the whole tuple would double every async collective.
            # (all-reduce-start returns its outputs alone.)
            parts = [p for p in parts if not p["context"]]
            parts = parts[len(parts) // 2:]
        out.append({
            "op": opcode,
            "bytes": sum(p["bytes"] for p in parts),
            "parts": [{"bytes": p["bytes"], "elems": p["elems"]}
                      for p in parts],
            "shape": re.sub(r"\{[^}]*\}", "", shape_text),
            "group_size": _group_size(line),
            "executions": executions.get(current, 1),
        })
    return out


def summarize_collectives(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """{opcode: {count, total_bytes, example_shape, group_size}} —
    ``count`` is ops in the text, ``total_bytes`` what one run of the module
    moves (an op in a loop body counts once per trip)."""
    summary: Dict[str, Dict[str, Any]] = defaultdict(
        lambda: {"count": 0, "total_bytes": 0, "example_shape": None,
                 "group_size": None})
    for rec in parse_collectives(hlo_text):
        s = summary[rec["op"]]
        s["count"] += 1
        s["total_bytes"] += rec["bytes"] * rec["executions"]
        if s["example_shape"] is None or rec["bytes"] > _shape_bytes(
                s["example_shape"] or ""):
            s["example_shape"] = rec["shape"]
        if rec["group_size"]:
            s["group_size"] = rec["group_size"]
    return dict(summary)


def summarize_compiled(compiled) -> Dict[str, Dict[str, Any]]:
    """Summary from a ``jax.stages.Compiled`` (or anything with
    ``as_text()``)."""
    return summarize_collectives(compiled.as_text())
