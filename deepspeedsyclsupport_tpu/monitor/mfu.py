"""MFU ledger core: region registry, HLO op→region map, trace join.

The step-time attribution instrument ("Exploring the limits of Concurrency
in ML Training on Google TPUs" does this per-phase attribution at pod
scale): the engine wraps model phases in ``jax.named_scope("mfu.<region>")``
labels, XLA propagates those labels into every compiled instruction's
``metadata={op_name=...}``, and the profiler's Chrome-trace window carries
one timed event per executed HLO op named by instruction. This module owns
the three joins between those worlds:

* :func:`build_opmap` — compiled-HLO text → ``{instruction: {region, pass,
  category, scope, root}}`` (the named_scope metadata is read here;
  collectives override to the ``collective`` region by opcode, since the
  partitioner inserts them with no scope; the same ``op_name`` path says
  which PASS of the step the instruction belongs to: forward, backward,
  remat's recomputed forward, and which :data:`SUB_SCOPES` label it lies
  under; ``root`` is what a fusion's fused computation ends in).
* :func:`publish` / :func:`published` — the map of the step the engine
  compiled, kept by program name (``train_batch_fn``; a serving forward's
  ``ragged_forward@<rows>``, one a static shape:
  ``InferenceEngineV2.published_programs()`` has the names) for whoever
  holds a trace of that program: :func:`ledger`'s caller,
  ``tools/mfu_report.py`` through the persisted ``mfu_opmap.json``, and the
  benchmark's ``train_*_ms`` and ``fwd_split_pct`` readers.
* :func:`parse_trace` — ``trace.json.gz`` (Chrome-trace) → timed op events,
  with truncation salvage: a torn gzip / half-written JSON from a killed
  run yields everything parseable plus a ``truncated`` flag, never a crash
  (the ``monitor/pod.py`` contract).
* :func:`ledger` — the MFU ledger itself: achieved MFU, the gap waterfall
  (hardware peak → roofline-achievable → measured), per-region
  measured-vs-achievable with bound-by verdicts, top time sinks, and the
  region-sum↔step-time reconciliation.

DELIBERATELY STDLIB-ONLY: ``tools/mfu_report.py`` loads this file by path
on jax-less login nodes (the ``pod.py`` contract — telemetry/analysis
import FROM here, never the reverse). :func:`region_scope` is the one
jax-touching helper and imports it lazily at call time.
"""
import gzip
import json
import os
import re
import zlib
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

#: Canonical attribution regions. The first block are SCOPE regions — model/
#: engine code wraps phases in ``jax.named_scope("mfu.<name>")`` (via
#: :func:`region_scope`) and dslint's ``undeclared-region`` rule rejects any
#: label outside this set. The rest are DERIVED: ``collective`` is assigned
#: by opcode (partitioner-inserted traffic carries no scope), ``host`` is
#: the measured step-wall minus device-busy gap, ``other`` is every mapped
#: op with no scope (norm chains, loss-scale bookkeeping, data movement).
SCOPE_REGIONS = ("embed", "attn", "mlp", "head", "loss", "optimizer")
DERIVED_REGIONS = ("collective", "other", "host")
REGIONS = SCOPE_REGIONS + DERIVED_REGIONS
#: the scope regions that are differentiated: their instructions run in one
#: of :data:`PASSES` (``optimizer`` runs once, after all three)
MODEL_REGIONS = SCOPE_REGIONS[:-1]
PASSES = ("fwd", "bwd", "recompute")

#: Named scopes INSIDE a region (:func:`scope`), for a reader that wants one
#: piece of a layer: the compiled program's ``op_name`` metadata carries the
#: label, the device trace only instruction names, so the reader maps label
#: to names from the program's own text (benchmark/metrics/moe_share_pct.py).
SUB_SCOPES = ("moe_route", "moe_experts", "moe_combine", "moe_shared",
              # latent attention (inference/v2/model.py): the down/up
              # projections with their norms, rotary and the pool write;
              # the absorption of W_UK into q and of W_UV out of the output
              "mla_proj", "mla_absorb",
              # hyper-connection streams: norm, maps, Sinkhorn, the mixes
              "mhc",
              # a Mamba-2 mixer (inference/v2/model.py, ops/ssm.py): the in
              # and out projections; the depthwise convolution and its tail;
              # the recurrence with its D skip (decode step or chunked
              # scan); the gate and its grouped norm; and, INSIDE ssm_scan,
              # the chunked scan's pieces apart from the one-token rows'
              # state step that a mixed round runs beside them
              "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_chunk",
              # a power-retention layer (inference/v2/model.py,
              # ops/retention.py): the q, k, v and output projections with
              # their norms and rotary; the gate's projection; the
              # recurrence (decode step or chunked form); and, INSIDE
              # ret_scan, the chunked form's pieces apart from the
              # one-token rows' state step beside them
              "ret_proj", "ret_gate", "ret_scan", "ret_chunk",
              # a gated delta-rule mixer (inference/v2/model.py,
              # ops/kda.py): the projections in and out; the depthwise
              # convolution and its tail; the decay, beta, the L2 norms and
              # the gated output norm; the recurrence, INSIDE which kda_step
              # is the one-token rows' state step and kda_chunk the chunked
              # form's pieces; and the output gate of the softmax attention
              # layers beside them (ModelConfig.attn_out_gate)
              "kda_proj", "kda_conv", "kda_gate", "kda_scan", "kda_step",
              "kda_chunk", "attn_gate",
              # a stack of two attention kinds (ModelConfig.attn_period):
              # a windowed layer's and a full layer's q, k, v, rotary, pool
              # write and attention, so a profile tells the kinds apart
              "attn_swa", "attn_full",
              # block-sparse attention chosen from pooled keys
              # (ModelConfig.sparse_block_topk; inference/v2/bsa.py,
              # ops/sparse_block.py): the pooled keys' write; the scores
              # and their pooling to blocks; the selection; whatever
              # gathers, masks and attends, INSIDE which bsa_rows is the
              # one-token rows' part
              "bsa_pool", "bsa_score", "bsa_select", "bsa_attend",
              "bsa_rows",
              # a lightning linear-attention mixer (inference/v2/model.py,
              # ops/ssm.py): the projections in and out; the head norms,
              # rotation, output norm and gate; the recurrence, INSIDE
              # which la_step is the one-token rows' state step and
              # la_chunk the chunked form's pieces
              "la_proj", "la_gate", "la_scan", "la_step", "la_chunk",
              # a layer of attention heads and a Mamba-2 mixer side by side
              # (layer_pattern's ``H``; inference/v2/model.py): the
              # attention half, projections, rotation, pool write, kernel
              # and output projection (the Mamba half keeps its ssm_*); and
              # the unembedding of every serving forward
              "h1_attn", "lm_head")

_SUB_SCOPES = frozenset(SUB_SCOPES)

#: named_scope label prefix — ``mfu.attn`` etc. Kept short and distinctive
#: so the metadata regex can't false-positive on user scopes.
SCOPE_PREFIX = "mfu."

_REGION_RE = re.compile(r"mfu\.([A-Za-z0-9_]+)")

#: HLO opcodes that are cross-device traffic regardless of scope (async
#: halves included — time is attributed to whichever half the runtime bills)
COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-reduce-done", "all-gather-start",
    "all-gather-done", "collective-permute-start",
    "collective-permute-done", "send", "recv", "send-done", "recv-done",
})

#: coarse HLO category buckets for the by-category time split
_CATEGORY = (
    ("dot", ("dot", "convolution")),
    ("collective", tuple(COLLECTIVE_OPCODES)),
    ("fusion", ("fusion",)),
    ("reduce", ("reduce", "reduce-window", "scatter", "gather")),
    ("data-movement", ("copy", "transpose", "broadcast", "reshape",
                       "bitcast", "concatenate", "slice", "dynamic-slice",
                       "dynamic-update-slice", "pad", "iota")),
    ("control", ("while", "conditional", "call", "tuple",
                 "get-tuple-element", "parameter", "constant")),
)


def region_scope(name: str):
    """``jax.named_scope`` for a declared MFU region — the ONE sanctioned
    way model/engine code labels a phase (a bare ``named_scope("mfu.x")``
    with a typo'd region would silently orphan its time; dslint's
    ``undeclared-region`` rule rejects it, and this helper raises)."""
    if name not in SCOPE_REGIONS:
        raise ValueError(f"undeclared MFU region {name!r}; declared scope "
                         f"regions: {SCOPE_REGIONS} (monitor/mfu.py)")
    import jax  # lazy: this module must import stdlib-only

    return jax.named_scope(SCOPE_PREFIX + name)


def scope(name: str):
    """``jax.named_scope`` for a declared sub-scope (:data:`SUB_SCOPES`):
    as :func:`region_scope`, a typo'd label raises instead of leaving its
    reader nothing to find."""
    if name not in SUB_SCOPES:
        raise ValueError(f"undeclared scope {name!r}; declared: "
                         f"{SUB_SCOPES} (monitor/mfu.py)")
    import jax  # lazy: this module must import stdlib-only

    return jax.named_scope(name)


def region_of(op_name: str) -> Optional[str]:
    """Region encoded in an HLO ``metadata op_name`` path (e.g.
    ``jit(f)/transpose(jvp(mfu.attn))/dot_general`` → ``attn``). The LAST
    match wins: an inner scope refines an outer one. ``None`` = unscoped."""
    found = _REGION_RE.findall(op_name or "")
    if not found:
        return None
    name = found[-1]
    return name if name in SCOPE_REGIONS else None


def scope_of(op_name: str) -> Optional[str]:
    """The innermost :data:`SUB_SCOPES` label among the ``/``-separated
    components of an HLO ``metadata op_name`` path (``.../mfu.mlp/
    moe_experts/ragged_dot`` → ``moe_experts``); ``None`` under none."""
    return next((part for part in reversed((op_name or "").split("/"))
                 if part in _SUB_SCOPES), None)


def pass_of(op_name: str, region: Optional[str]) -> Optional[str]:
    """Pass of the training step an HLO ``metadata op_name`` path lies in,
    by its ``/``-separated components: ``recompute`` where one is
    ``rematted_computation`` (what ``jax.checkpoint`` names the forward it
    runs again inside the backward), else ``bwd`` where one starts with
    ``transpose(``, else ``fwd`` where ``region`` is a model region, else
    ``None`` (the update, plumbing outside the differentiated function).

    Matched on JAX 0.9.0: forward ``jit(f)/jvp(mfu.mlp)/dot_general`` or,
    under a scanned trunk, ``jit(f)/jvp()/while/body/closed_call/mfu.mlp/..``;
    backward ``jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/
    mfu.mlp/..``; the recomputed forward ``../checkpoint/
    rematted_computation/mfu.mlp/..``; the update ``jit(f)/mfu.optimizer/..``.
    The CPU's and the TPU's (libtpu 0.0.34) compiled text print the same
    paths: the metadata is the lowering's, not the backend's."""
    parts = (op_name or "").split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "bwd"
    return "fwd" if region in MODEL_REGIONS else None


def _category_of(opcode: str) -> str:
    for cat, ops in _CATEGORY:
        if opcode in ops:
            return cat
    return "other"


# one HLO instruction definition: `  %name = type opcode(...), ...` or
# `  ROOT %name = ...`, the `%` there or not (as_text() prints it, a dump with
# print_percent off does not). Names may carry dots/dashes (`dot.12`,
# `subtract_exponential_fusion`); the result type may be a parenthesized
# TUPLE with internal spaces — `(f32[8]{0}, s32[])` — which is exactly what
# `while` loops and combined (variadic) all-reduces produce, i.e. the scan
# trunk and the main grad-sync traffic this instrument exists to name. On
# TPU the tuple nests (an `async-start` returns `((operands), result,
# s32[])`) and its layouts carry parens of their own
# (`bf16[4,2048]{1,0:T(8,128)(2,1)S(1)}`), so the tuple branch does not
# count them: it ends at the first `)` that whitespace and `opcode(` follow,
# and inside a type no `)` is followed by whitespace.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(?:\(.*?\)|[^\s(]\S*)\s+"
    r"([a-z][\w\-]*)\(")
_METADATA_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


# a computation's header, `%fused_computation.3 (param_0: f32[8]) -> f32[8] {`
# (or `ENTRY %main ...`), and what a fusion's line says it runs
_COMPUTATION_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"[\w.\-]+")   # (and a type's tokens: no names)
#: opcodes a fusion's ROOT is seen THROUGH to what they wrap: a `bitcast`
#: moves nothing, and a multi-output fusion ends in a `tuple` of its results
_ROOT_THROUGH = ("bitcast", "tuple")


def _root_of(computation: Dict[str, Tuple[str, str]], name: str,
             seen: int = 0) -> str:
    """Opcode(s) the instruction ``name`` of ``computation`` (``{name:
    (opcode, its operands as the line spells them)}``) comes to: its own, or
    through a ``bitcast`` / ``tuple`` its operands' (several that differ are
    joined by ``+`` in their order: a fusion with two results of two kinds
    is neither)."""
    opcode, operands = computation[name]
    if opcode not in _ROOT_THROUGH or seen > 8:
        return opcode
    inner = [o for o in _OPERAND_RE.findall(operands) if o in computation
             and computation[o][0] != "parameter"]
    return "+".join(dict.fromkeys(
        _root_of(computation, o, seen + 1) for o in inner)) or opcode


def build_opmap(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Compiled-HLO text → ``{instruction_name: {"region", "pass",
    "category", "opcode", "op_name", "scope", "root"}}`` for every
    instruction in every computation (trace events are named by instruction;
    names are unique module-wide).

    Region precedence: collective opcode > ``mfu.<region>`` scope in the
    op_name metadata > ``other``. ``pass`` is :func:`pass_of` of the same
    path (a fusion's line carries its root's path, so a fusion is its
    root's region and pass) and ``scope`` :func:`scope_of` of it. ``root``:
    of a fusion, the opcode its fused computation's ROOT comes to
    (:func:`_root_of`: a ``copy``, a ``pad`` behind its producer's convert,
    a ``convolution``), of any other instruction its own opcode; a reader
    that asks what only MOVES data asks it. Trivial bookkeeping opcodes
    (parameter/constant/tuple plumbing) are skipped — they never carry
    measured time.
    """
    out: Dict[str, Dict[str, Any]] = {}
    roots: Dict[str, str] = {}          # computation -> what its ROOT is
    fused: Dict[str, str] = {}          # fusion instruction -> computation
    current, body = None, {}            # the computation the lines stand in
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            c = _COMPUTATION_RE.match(line)
            if c:
                current, body = c.group(1), {}
            continue
        name, opcode = m.group(1), m.group(2)
        body[name] = (opcode, line[m.end():].split(")", 1)[0])
        if line.lstrip().startswith("ROOT "):
            roots[current] = _root_of(body, name)
        if opcode in ("parameter", "constant", "tuple", "get-tuple-element"):
            continue
        meta = _METADATA_RE.search(line)
        path = meta.group(1) if meta else ""
        if opcode in COLLECTIVE_OPCODES:
            region = "collective"
        else:
            region = region_of(path) or "other"
        if opcode == "fusion":
            calls = _CALLS_RE.search(line)
            if calls:
                fused[name] = calls.group(1)
        out[name] = {"region": region, "pass": pass_of(path, region),
                     "category": _category_of(opcode), "opcode": opcode,
                     "op_name": path, "scope": scope_of(path),
                     "root": opcode}
    for name, computation in fused.items():
        out[name]["root"] = roots.get(computation, "fusion")
    return out


# the compiled step of each program name, as the engine last handed it over;
# replaced by its opmap the first time somebody asks (as_text() and the parse
# of mistral-7b-d2's step took 44 ms on the v5e: a run that never asks pays
# nothing)
_PUBLISHED: Dict[str, Any] = {}
_RECORDS: Dict[str, Dict[str, Any]] = {}


def publish(program: str, compiled: Any, **record: Any) -> None:
    """Keep ``compiled`` (anything with ``as_text()``) as the LAST compiled
    step of ``program`` — the name the profiler's ``XLA Modules`` line gives
    it, less ``jit_``. ``Engine.compiled_train_step()`` calls this, and
    hands with it what the program cannot say of itself (``remat``: the rung
    its checkpointed layers took and the bytes they save): :func:`step_record`."""
    _PUBLISHED[program] = compiled
    _RECORDS[program] = record


def step_record(program: str) -> Dict[str, Any]:
    """What was published beside the last step of ``program``; empty where
    nothing was."""
    return _RECORDS.get(program, {})


def published(program: str) -> Optional[Dict[str, Dict[str, Any]]]:
    """:func:`build_opmap` of the last step published under ``program``;
    ``None`` where nothing was."""
    entry = _PUBLISHED.get(program)
    if entry is not None and not isinstance(entry, dict):
        entry = _PUBLISHED[program] = build_opmap(entry.as_text())
    return entry


# ------------------------------------------------------------------ trace IO
def _salvage_events(text: str) -> Tuple[List[Dict[str, Any]], bool]:
    """Chrome-trace JSON salvage: when ``json.loads`` fails (torn tail),
    walk the ``traceEvents`` array with a brace counter and keep every
    COMPLETE event object. Returns (events, salvaged_flag)."""
    try:
        d = json.loads(text)
        return list(d.get("traceEvents", [])), False
    except ValueError:
        pass
    events: List[Dict[str, Any]] = []
    idx = text.find('"traceEvents"')
    if idx < 0:
        return events, True
    idx = text.find("[", idx)
    if idx < 0:
        return events, True
    depth = 0
    start = None
    in_str = False
    esc = False
    for i in range(idx + 1, len(text)):
        c = text[i]
        if in_str:
            if esc:
                esc = False
            elif c == "\\":
                esc = True
            elif c == '"':
                in_str = False
            continue
        if c == '"':
            in_str = True
        elif c == "{":
            if depth == 0:
                start = i
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0 and start is not None:
                try:
                    events.append(json.loads(text[start:i + 1]))
                except ValueError:
                    pass
                start = None
        elif c == "]" and depth == 0:
            break
    return events, True


def parse_trace(path: str) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """Load one Chrome-trace file (``.json`` or ``.json.gz``) with
    truncation salvage. Returns ``(duration_events, meta)`` where
    duration_events are the ``"ph" == "X"`` records and ``meta`` carries
    ``{"truncated": bool, "n_events": int, "path": str}``. A torn gzip
    stream (killed mid-write) decompresses to its last whole deflate block
    and the JSON salvage keeps every complete event — flagged, not fatal."""
    truncated = False
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return [], {"truncated": True, "n_events": 0, "path": path}
    if path.endswith(".gz") or raw[:2] == b"\x1f\x8b":
        try:
            text = gzip.decompress(raw).decode("utf-8", "replace")
        except (OSError, EOFError, zlib.error):
            # torn gzip: stream-decompress whatever whole blocks exist
            d = zlib.decompressobj(wbits=31)
            try:
                text = d.decompress(raw).decode("utf-8", "replace")
            except zlib.error:
                text = ""
            truncated = True
    else:
        text = raw.decode("utf-8", "replace")
    events, salvaged = _salvage_events(text)
    truncated = truncated or salvaged
    dur_events = [e for e in events
                  if e.get("ph") == "X" and "ts" in e and "dur" in e]
    return dur_events, {"truncated": truncated, "n_events": len(dur_events),
                        "path": path}


def find_trace(root: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` (or ``trace.json``) under ``root`` — the
    ``jax.profiler`` layout is ``<root>/plugins/profile/<run>/<host>.trace
    .json.gz``; a bare file path passes through."""
    if os.path.isfile(root):
        return root
    hits: List[str] = []
    for dirpath, _dirnames, files in os.walk(root):
        for f in files:
            if f.endswith((".trace.json.gz", "trace.json.gz", "trace.json")):
                hits.append(os.path.join(dirpath, f))
    return max(hits, key=lambda p: os.path.getmtime(p)) if hits else None


# ---------------------------------------------------------------- measurement
def _union_us(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals (µs)."""
    ivs = sorted(intervals)
    total = 0.0
    cur_s = cur_e = None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _self_segments(events: List[Dict[str, Any]],
                   opmap: Dict[str, Dict[str, Any]]
                   ) -> List[Tuple[float, float, Dict[str, Any]]]:
    """Flatten one THREAD's (properly nested) op events into disjoint
    ``(start, end, opmap entry)`` self-time segments: a ``while`` op's
    event covers its whole loop while every body op is ALSO recorded inside
    it — a plain duration sum double-counts that containment (observed
    1.7× on the CPU executor). Each event owns only the parts of its span
    not covered by a nested event."""
    es = sorted((e for e in events), key=lambda e: (e["ts"], -e["dur"]))
    segs: List[Tuple[float, float, Dict[str, Any]]] = []
    # stack entries: [end, cursor, entry]; cursor = where this event's
    # uncovered span resumes after the current child
    stack: List[List[Any]] = []

    def pop_to(ts: float) -> None:
        while stack and stack[-1][0] <= ts:
            end, cursor, info = stack.pop()
            if end > cursor:
                segs.append((cursor, end, info))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for e in es:
        ts = float(e["ts"])
        end = ts + float(e["dur"])
        pop_to(ts)
        if stack and stack[-1][1] < ts:
            # parent's uncovered span up to this child
            segs.append((stack[-1][1], ts, stack[-1][2]))
            stack[-1][1] = ts
        stack.append([end, ts, opmap[str(e["name"])]])
    pop_to(float("inf"))
    return segs


def measure_regions(events: Sequence[Dict[str, Any]],
                    opmap: Dict[str, Dict[str, Any]],
                    steps: int = 1) -> Dict[str, Any]:
    """Join timed trace events against the opmap into per-region, per-pass
    and per-HLO-category seconds (per step).

    Attribution is WALL-CLOCK-exact, not duration-sum: per thread, nested
    events flatten to self-time segments (:func:`_self_segments`); across
    threads, every instant of the mapped-op union timeline is split evenly
    among the threads busy at that instant (the executor genuinely runs
    independent ops concurrently — billing both in full would overcount).
    So ``sum(regions) == mapped-op union`` by construction, and the ledger
    reconciliation catches the one thing that can still go missing:
    op events whose name is NOT in the opmap (``orphan_s``) — exactly what
    a typo'd/missing scope or a stale opmap produces (the Chrome trace names
    an op event by the bare instruction, on the CPU executor and on a TPU's
    ``XLA Ops`` line alike; only the ``.xplane.pb`` carries the whole text).
    ``passes`` is ``{region: {pass or "-": seconds}}``, each region's row
    re-summing to its ``regions`` entry.

    ``device_busy_s`` is the union over ALL op events (an event counts as
    an op when its name is in the opmap or it carries the arg the
    profiler gives an op: ``hlo_op`` on the CPU executor's events,
    ``hlo_category`` on a TPU's ``XLA Ops`` line), mapped or not."""
    steps = max(1, int(steps))
    by_thread: Dict[Tuple[Any, Any], List[Dict[str, Any]]] = {}
    all_intervals: List[Tuple[float, float]] = []
    n_mapped = n_orphan = 0
    for e in events:
        mapped = str(e.get("name", "")) in opmap
        args = e.get("args") or {}
        is_op = mapped or "hlo_op" in args or "hlo_category" in args
        if not is_op:
            continue
        ts = float(e["ts"])
        all_intervals.append((ts, ts + float(e["dur"])))
        if not mapped:
            n_orphan += 1
            continue
        n_mapped += 1
        by_thread.setdefault((e.get("pid"), e.get("tid")), []).append(e)

    # per-thread disjoint self segments → global even-split sweep
    points: List[Tuple[float, int, int, Dict[str, Any]]] = []
    for ti, es in enumerate(by_thread.values()):
        for s, e, info in _self_segments(es, opmap):
            points.append((s, 1, ti, info))
            points.append((e, -1, ti, info))
    # closes (-1) before opens (+1) at equal t: per-thread segments are
    # disjoint, so a segment ending exactly where the next begins must
    # release the thread slot before the successor claims it
    points.sort(key=lambda p: (p[0], p[1]))
    regions: Dict[str, float] = {}
    categories: Dict[str, float] = {}
    passes: Dict[str, Dict[str, float]] = {}
    active: Dict[int, Dict[str, Any]] = {}
    prev = None
    mapped_union = 0.0
    for t, kind, ti, info in points:
        if prev is not None and active and t > prev:
            share = (t - prev) / len(active)
            mapped_union += t - prev
            for a in active.values():
                r, c, p = a["region"], a["category"], a.get("pass") or "-"
                regions[r] = regions.get(r, 0.0) + share
                categories[c] = categories.get(c, 0.0) + share
                row = passes.setdefault(r, {})
                row[p] = row.get(p, 0.0) + share
        prev = t
        if kind == 1:
            active[ti] = info
        else:
            active.pop(ti, None)

    union_all = _union_us(all_intervals)
    return {
        "regions": {r: s / 1e6 / steps for r, s in regions.items()},
        "categories": {c: s / 1e6 / steps for c, s in categories.items()},
        "passes": {r: {p: s / 1e6 / steps for p, s in row.items()}
                   for r, row in passes.items()},
        "device_busy_s": union_all / 1e6 / steps,
        "mapped_union_s": mapped_union / 1e6 / steps,
        "orphan_s": max(0.0, union_all - mapped_union) / 1e6 / steps,
        "n_mapped": n_mapped,
        "n_unmapped": n_orphan,
        "steps": steps,
    }


# -------------------------------------------------------------------- ledger
#: serialized-ledger schema (validated by tests and the report tool)
MFU_LEDGER_KEYS = ("schema_version", "step_s", "device_busy_s", "host_s",
                   "orphan_s", "model_flops", "peak_flops", "achieved_mfu",
                   "roofline_mfu", "waterfall", "regions", "top_sinks",
                   "reconciliation", "truncated_trace", "device")


def ledger(roofline: Optional[Dict[str, Any]],
           measured: Dict[str, Any],
           step_s: float,
           truncated_trace: bool = False) -> Dict[str, Any]:
    """The join: analytic roofline table + measured per-region times + the
    measured clean-step wall → the MFU ledger.

    ``roofline`` is ``analysis/roofline.py``'s serialized table
    (``{"device", "spec": {"peak_flops", ...}, "regions": {r: {"flops",
    "hbm_bytes", "comm_bytes", "achievable_s", "bound_by"}},
    "total_flops", "total_achievable_s"}``) — optional: without it the
    ledger is measured-only (no waterfall/verdicts), which is what a bare
    trace on a login node can still say.

    Waterfall semantics: ``hardware_peak`` is the time the step's analytic
    FLOPs would take at 100% MFU; ``roofline_achievable`` adds each
    region's binding resource (compute, HBM bytes, or comm bytes — the
    per-region max, summed, an optimistic no-overlap-needed floor);
    ``measured`` is the observed clean-step wall. Each level carries the
    MFU the step WOULD run at if time stopped there, so gap = distance
    between adjacent bars and names whether the model (peak→roofline) or
    the execution (roofline→measured) loses the time.

    Reconciliation: region times (``host`` = step wall − device-busy union,
    included) must re-sum to the step wall. Region attribution is
    wall-exact (``measure_regions``), so the frac moves away from 1.0 for
    exactly two reasons: ORPHANED op time (measured ops whose name the
    opmap doesn't know — a typo'd scope, a stale opmap) pushes it low, and
    a window that measured MORE than the claimed step (two steps fused,
    wrong window) pushes it high."""
    step_s = max(float(step_s), 1e-12)
    meas_regions = dict(measured.get("regions", {}))
    device_busy = float(measured.get("device_busy_s", 0.0))
    host_s = max(0.0, step_s - device_busy)
    meas_regions["host"] = host_s
    spec = (roofline or {}).get("spec", {})
    peak = float(spec.get("peak_flops", 0.0))
    total_flops = float((roofline or {}).get("total_flops", 0.0))
    roof_regions = (roofline or {}).get("regions", {})
    roof_total_s = float((roofline or {}).get("total_achievable_s", 0.0))

    regions_out: Dict[str, Dict[str, Any]] = {}
    for name in sorted(set(meas_regions) | set(roof_regions)):
        meas = float(meas_regions.get(name, 0.0))
        roof = roof_regions.get(name, {})
        achievable = float(roof.get("achievable_s", 0.0))
        regions_out[name] = {
            "measured_s": meas,
            "frac": meas / step_s,
            "achievable_s": achievable,
            # measured/achievable: how far this region runs above its own
            # roofline floor (1.0 = at the roofline; 50 = 50x headroom)
            "headroom": (meas / achievable) if achievable > 0 else None,
            "bound_by": roof.get("bound_by"),
            "flops": float(roof.get("flops", 0.0)),
            "hbm_bytes": float(roof.get("hbm_bytes", 0.0)),
            "comm_bytes": float(roof.get("comm_bytes", 0.0)),
        }

    achieved_mfu = (total_flops / (step_s * peak)) if peak > 0 else None
    roofline_mfu = (total_flops / (roof_total_s * peak)
                    if peak > 0 and roof_total_s > 0 else None)
    waterfall = []
    if peak > 0 and total_flops > 0:
        peak_s = total_flops / peak
        waterfall = [
            {"level": "hardware_peak", "s": peak_s, "mfu": 1.0},
            {"level": "roofline_achievable", "s": roof_total_s,
             "mfu": roofline_mfu},
            {"level": "measured", "s": step_s, "mfu": achieved_mfu},
        ]
    sinks = sorted((r for r in regions_out if r != "host"),
                   key=lambda r: -regions_out[r]["measured_s"])
    region_sum = sum(v["measured_s"] for v in regions_out.values())
    return {
        "schema_version": 1,
        "step_s": step_s,
        "device_busy_s": device_busy,
        "host_s": host_s,
        "orphan_s": float(measured.get("orphan_s", 0.0)),
        "model_flops": total_flops,
        "peak_flops": peak,
        "achieved_mfu": achieved_mfu,
        "roofline_mfu": roofline_mfu,
        "waterfall": waterfall,
        "regions": regions_out,
        "top_sinks": sinks[:5],
        "reconciliation": {"region_sum_s": region_sum, "step_s": step_s,
                           "frac": region_sum / step_s},
        "truncated_trace": bool(truncated_trace),
        "device": (roofline or {}).get("device"),
        "categories": dict(measured.get("categories", {})),
        "passes": dict(measured.get("passes", {})),
        "n_mapped": int(measured.get("n_mapped", 0)),
        "n_unmapped": int(measured.get("n_unmapped", 0)),
    }


def validate_ledger(d: Dict[str, Any]) -> List[str]:
    """Missing-key check against :data:`MFU_LEDGER_KEYS` (schema v1)."""
    return [k for k in MFU_LEDGER_KEYS if k not in d]


def ledger_events(led: Dict[str, Any], step: int = 0
                  ) -> List[Tuple[str, Any, int]]:
    """Strict-registry ``MFU/*`` scalar events from a ledger (dot-tail
    region members — ``MFU/region.attn`` — so the static event-name lint
    resolves every literal)."""
    ev: List[Tuple[str, Any, int]] = [
        ("MFU/step_s", led["step_s"], step),
        ("MFU/device_busy_s", led["device_busy_s"], step),
    ]
    if led.get("achieved_mfu") is not None:
        ev.append(("MFU/achieved", led["achieved_mfu"], step))
    if led.get("roofline_mfu") is not None:
        ev.append(("MFU/roofline_bound", led["roofline_mfu"], step))
    if led.get("model_flops"):
        ev.append(("MFU/model_tflops", led["model_flops"] / 1e12, step))
    for name in REGIONS:
        r = led["regions"].get(name)
        if r is not None:
            # members enumerated from REGIONS, each declared exactly in
            # EVENT_NAMES — the base below never ships a typo'd member
            ev.append((f"MFU/region.{name}",  # dslint: allow(undeclared-event-name) registry-enumerated member builder
                       r["measured_s"], step))
    return ev


# -------------------------------------------------------------------- render
def _fmt_s(sec: Optional[float]) -> str:
    if sec is None:
        return "     -"
    if sec < 1e-3:
        return f"{sec * 1e6:.0f}us"
    return f"{sec * 1000:.1f}ms" if sec < 1.0 else f"{sec:.2f}s"


def _fmt_pct(x: Optional[float]) -> str:
    return "    -" if x is None else f"{100.0 * x:5.1f}%"


def render_ledger(led: Dict[str, Any], top: int = 10) -> str:
    """Human-readable ledger: waterfall, per-region table, top sinks."""
    lines = ["MFU ledger" + (f" — device {led['device']}"
                             if led.get("device") else "")]
    if led.get("truncated_trace"):
        lines.append("  WARNING: trace window was truncated — measured "
                     "times are a lower bound")
    if led.get("achieved_mfu") is not None:
        lines.append(f"  achieved MFU: {_fmt_pct(led['achieved_mfu'])} "
                     f"({led['model_flops'] / 1e12:.3f} TFLOP analytic step "
                     f"in {_fmt_s(led['step_s'])})")
    if led.get("waterfall"):
        lines.append("  gap waterfall (where would the step be if time "
                     "stopped at each level):")
        for w in led["waterfall"]:
            lines.append(f"    {w['level']:<22}{_fmt_s(w['s']):>10}  "
                         f"MFU {_fmt_pct(w.get('mfu'))}")
    regions = led.get("regions", {})
    if regions:
        lines.append(f"  {'region':<12}{'measured':>10}{'share':>8}"
                     f"{'roofline':>10}{'headroom':>10}  bound by")
        order = sorted(regions, key=lambda r: -regions[r]["measured_s"])
        for name in order:
            r = regions[name]
            if r["measured_s"] <= 0 and r["achievable_s"] <= 0:
                continue
            head = (f"{r['headroom']:8.1f}x" if r.get("headroom")
                    else "       -")
            lines.append(
                f"  {name:<12}{_fmt_s(r['measured_s']):>10}"
                f"{_fmt_pct(r['frac']):>8}{_fmt_s(r['achievable_s']):>10}"
                f"{head:>10}  {r.get('bound_by') or '-'}")
    sinks = led.get("top_sinks", [])[:top]
    if sinks:
        lines.append("  top sinks: " + ", ".join(
            f"{s} ({_fmt_s(regions[s]['measured_s'])})" for s in sinks))
    rec = led.get("reconciliation", {})
    if rec:
        frac = rec.get("frac", 0.0)
        flag = "" if abs(frac - 1.0) <= 0.05 else \
            "  <-- regions do not re-sum to the step (orphaned ops or " \
            "wrong window)"
        lines.append(f"  reconciliation: region sum "
                     f"{_fmt_s(rec.get('region_sum_s'))} vs step "
                     f"{_fmt_s(rec.get('step_s'))} "
                     f"({_fmt_pct(frac)} accounted){flag}")
        if led.get("orphan_s"):
            lines.append(f"  orphaned op time (not in opmap): "
                         f"{_fmt_s(led['orphan_s'])}")
    passes = led.get("passes", {})
    if passes:
        cols = PASSES + ("-",)
        lines.append(f"  {'region x pass':<14}" + "".join(
            f"{c:>11}" for c in cols))
        for name in sorted(passes, key=lambda r: -sum(passes[r].values())):
            lines.append(f"  {name:<14}" + "".join(
                f"{_fmt_s(passes[name].get(c) or None):>11}" for c in cols))
    cats = led.get("categories", {})
    if cats:
        order = sorted(cats, key=lambda c: -cats[c])
        lines.append("  by HLO category: " + ", ".join(
            f"{c}={_fmt_s(cats[c])}" for c in order if cats[c] > 0))
    return "\n".join(lines)
