"""Request-time attribution: stage registry, journal join, TTFT/ITL waterfall.

The serving-side sibling of the MFU ledger (``monitor/mfu.py``):
``Serve/ttft_s`` p95 says a request was slow, not whether edge admission,
router queueing, replica spool transport, chunked prefill, decode
rounds, preemption/requeue or failover replay ate the budget. This module
owns the three pieces that answer it:

* **stage registry** — :data:`SERVE_STAGES` / :data:`FLEET_STAGES`, the
  canonical lifecycle-stage names. ``ServingSession``/``RequestJournal``
  stamp ``serve/stage`` records and ``FleetRouter`` stamps ``fleet/stage``
  records with these literals riding the EXISTING journal / flight-recorder
  streams (no second transport); ``monitor/telemetry.py`` enumerates the
  strict ``Serve/stage.*`` / ``Fleet/stage.*`` event families from these
  tuples, and dslint's ``undeclared-stage-name`` rule rejects any literal
  outside them (the ``undeclared-region`` pattern).
* **join** — :func:`join_traces` fuses the router stream + per-replica
  journals (uid-keyed, wall-``t`` ordered, torn-tail salvaged) into
  per-request span trees that survive generation respawns and failover:
  a replayed request's trace spans the dead replica's segment and the
  survivor's replay segment. Stage self-times are a telescoping partition
  of the request's timeline, so the reconciliation contract holds by
  construction: stage sums match the journal-observed enqueue→close wall
  time within 5%, residual reported as ``unattributed``.
* **attribution** — :func:`attribution`: TTFT and ITL decomposed per stage
  at p50/p95/p99, tail attribution (which stage grew for the slowest
  decile vs the median cohort), SLO burn over sliding windows, and the
  N worst requests' waterfalls — the payload ``tools/trace_report.py
  --requests`` renders.

DELIBERATELY STDLIB-ONLY: ``tools/trace_report.py`` loads this file by path
on jax-less login nodes (the ``pod.py``/``mfu.py`` contract —
telemetry/serving import FROM here, never the reverse).
"""
import contextlib
import glob as _glob
import json
import math
import os
import re
from typing import (Any, Dict, Iterable, List, Optional, Sequence, Tuple)

#: Canonical replica-side lifecycle stages. The first block are STAMPED —
#: ``ServingSession``/``serve_worker`` write ``serve/stage`` records with
#: these literals (dslint's ``undeclared-stage-name`` rule rejects any
#: other). The rest are DERIVED by the join from the emit/close stream:
#: ``decode`` from inter-emit gaps, ``finalize`` (last emit → close),
#: ``unattributed`` (any interval the classifier cannot name — the
#: reconciliation residual). ``round`` is the session-scope record of one
#: scheduling round (uid -1: its times, what it launched, its
#: :data:`ROUND_PHASES`); ``decode_round`` is what older journals carry in
#: its place and only the join still reads.
STAMPED_SERVE_STAGES = ("gate", "queue_wait", "requeue_wait", "prefill",
                        "prefill_chunk", "decode_round", "round", "preempt",
                        "replay", "spool_wait")
DERIVED_SERVE_STAGES = ("decode", "finalize", "unattributed")
SERVE_STAGES = STAMPED_SERVE_STAGES + DERIVED_SERVE_STAGES

#: Router-side stages (``fleet/stage`` records). ``transport`` is derived:
#: the route→replica-admit gap (spool wait + process hop for
#: ``ProcessReplica``; ~0 in-process).
STAMPED_FLEET_STAGES = ("edge_gate", "placement", "failover_claim",
                        "replay_segment")
DERIVED_FLEET_STAGES = ("transport",)
FLEET_STAGES = STAMPED_FLEET_STAGES + DERIVED_FLEET_STAGES

#: Stages whose per-request self-time the session observes into
#: ``Serve/stage.<name>_s`` histograms at close (queue wait has its own
#: satellite family, ``Serve/queue_wait_s``).
STAGE_HISTOGRAMS = ("prefill", "decode")

#: Phases of one scheduling round (``ServingSession.step``), in the order a
#: per-token round passes them: it launches the sampler over the LAST
#: forward's logits, plans and launches the NEXT forward (whose decode rows
#: take their tokens from the sampler's output on the device), and only then
#: reads the sampled tokens back and hands them out. The session's
#: ``RoundSpans`` charges every
#: instant of the round to exactly one of them (``other`` is the residual),
#: opens a ``dstpu/serve/<phase>`` profiler annotation around each, and
#: writes their seconds into the ``round`` stage record's ``phases``. A
#: phase is time on the HOST's clock, not host work alone: ``readback``
#: asks the device for a value (the tokens the sampler drew behind the last
#: forward: it holds what is left of that forward's time, while the forward
#: this round launched already waits behind it on the device), and ANY launch
#: blocks while the device's launch queue is full. A round launches three
#: programs (the key's split, the sampler, the forward), so the queue does
#: not fill. What the host costs the device is read off a profile: the idle
#: gaps at the round's ends and its number of launches.
ROUND_PHASES = (
    "queue",      # _maintain_queue, slack policy, watchdog arm
    "gather",     # who has drained (host), rng split, the [S] slot vector
    "sample",     # the sampler's dispatch and the start of its copy to the host
    "schedule",   # budgets that end, KV-pressure loop, check_schedule, schedule_chunks, CoW
    "build",      # build_ragged_batch / _slot_arrays (numpy only)
    "dispatch",   # host-to-device copies + the forward's launch, until it returns
    "collect",    # put() after the launch: descriptors, logits handles, prefix index
    "readback",   # np.asarray(tokens): the round asks the device for a value
    "emit",       # events, _note_emission, _finish/flush, evictions said
    "account",    # prefill_chunk stamps, capacity samples, progress valve, gauges
    "other")      # whatever no phase claimed

#: What a ``round`` record says of the forward it launched, counted before
#: the launch: sequences, tokens, how many of those were prompt (a chunk is
#: a decode step when it is one token on top of cached context), the cached
#: tokens attention must read, and the blocks in the sequences' tables;
#: then the tiles attention ran them in: ``decode_rows``, the one-token
#: chunks (a one-row tile each; every row of a ``decode_forward``), and
#: ``atoms``, the live ``atom_q_size``-row tiles the longer chunks of a
#: ``ragged_forward`` were cut into (0 where the attention takes no atoms);
#: ``warm_tiles``, those of both kinds whose first KV step the tile before
#: them in their kernel call's grid fetched (``ops.paged_attention``'s
#: hand-over: the live tiles less one a call and one a dead gap; head tiles
#: of one atom, always warm behind its first, are not counted);
#: and what those tiles cover (``ragged.attention_work``): ``attn_pairs``,
#: the (row, cached token) pairs of the chunks of two tokens or more, and
#: ``dec_ctx_tokens``, the one-token chunks' context lengths; and what the
#: kernels' loops walk for it, summed over the atoms and the one-row tiles:
#: ``kv_tile_keys``, the keys each tile may see, and ``kv_step_keys``, those
#: rounded up to whole loop steps (several KV blocks on a latent pool).
#: ``rows`` is the static row count the forward ran at, pads included: the
#: shape a ``ragged_forward``'s batch was built at (the smallest of the
#: engine's ``ragged.ragged_shapes`` that held the round), ``max_sequences``
#: for a ``decode_forward``; ``tokens`` / ``rows`` is how full it was.
#: ``moe_touched`` is the one field the DEVICE counts (a sparse-expert
#: model's experts with at least one live row, summed over layers: the
#: expert weights a forward had to read; 0 for a dense model). It comes back
#: behind the sampled tokens, so a record carries the count of the forward
#: whose logits its round SAMPLED: the launch of the record before it.
#: ``ahead`` is 1 where the forward was dispatched BEFORE the last forward's
#: sampled tokens were read back (every round but the first after
#: idle, which has nothing to read), and ``spec_rows``
#: counts its rows that belonged to a stream which had already ended: on an
#: EOS, which the host learns at the read-back, one forward late.
FORWARD_FIELDS = ("n_seqs", "tokens", "prefill_tokens", "ctx_tokens",
                  "kv_blocks", "decode_rows", "atoms", "attn_pairs",
                  "dec_ctx_tokens", "moe_touched", "ahead", "spec_rows",
                  "kv_step_keys", "kv_tile_keys", "rows", "warm_tiles")
#: What the device counts, in the order it rides behind the sampled tokens
#: (``engine.moe_tail``). ``moe_rows`` is on the record ONLY of a program
#: that holds a share of the router's experts (one chip of an expert-
#: parallel layer): the (token, choice) rows that went through the experts
#: held here, summed over layers; ``moe_touched`` then counts the held
#: experts with a row. A program that holds every expert leaves it out: each
#: live token brings ``num_experts_per_tok`` rows a layer, as a reader knows.
#: ``moe_tiles``: the row tiles the forward's grouped GEMMs visited, summed
#: over layers: each held expert's rows rounded up to whole tiles of the
#: forward's ``moe_tile_rows`` (``ops.grouped_gemm.row_tile``: static by its
#: shape). rows / (``moe_tiles`` x ``moe_tile_rows``) is how full the tiles
#: were, ``moe_tiles`` / ``moe_touched`` the visits an expert's weights served.
MOE_TAIL_FIELDS = ("moe_touched", "moe_tiles", "moe_rows")
#: What a sparse-expert model's record says of the forward it LAUNCHED that
#: no count is: the rows of a tile of its grouped GEMMs, and the (token,
#: choice) rows a live token brings them over all expert layers where every
#: expert is held (``num_experts_per_tok`` x expert layers). A dense model's
#: record has neither.
MOE_STATIC_FIELDS = ("moe_tile_rows", "moe_rows_a_token")
#: What the record of a looped stack (``ModelConfig.total_ut_steps`` > 1)
#: says besides: ``passes``, how many times the forward it launched runs the
#: layers, and ``kv_rows``, the cache rows it writes and attends a token
#: (passes x layers); and, counted on the DEVICE and so a record late as the
#: ``moe_*`` counts are, ``exit_pass`` [passes]: the rows unembedded for a
#: live sequence by the pass the exit rule took their logits from, SUMMED
#: since the engine was built (at the published threshold all in the last).
#: Every other model's record has none of the three.
LOOP_FIELDS = ("passes", "kv_rows", "exit_pass")
#: What the record of a model with a sparse-attention indexer
#: (``ModelConfig.index_topk``) says besides, counted on the host before the
#: launch (``ragged.selection_work``): ``sel_pairs``, the (row, SELECTED
#: token) pairs of the chunks of two tokens or more, and ``dec_sel_tokens``,
#: the selected tokens of the one-token chunks: what the attention reads of
#: ``attn_pairs`` and ``dec_ctx_tokens``; and ``dec_walk_keys``, the keys the
#: indexer's two steps WALK for the one-token chunks: each row's context
#: rounded up to its scores step, plus a row the longest's rounded up to the
#: selection's chunk (over 2 x ``decode_rows`` x the table's width: the
#: share of the score matrix's columns still paid; 1 on a route that takes
#: no kernels). Every other model's record has none of the three.
DSA_FIELDS = ("sel_pairs", "dec_sel_tokens", "dec_walk_keys")
#: What the record of a stack of two attention kinds
#: (``ModelConfig.attn_period``: windowed layers and full ones, a pool each)
#: says besides, counted on the host before the launch:
#: ``kv_full_blocks_held`` and ``kv_window_blocks_held``, the blocks of each
#: pool that are a sequence's now, this forward's new ones among them;
#: ``kv_live_ctx_tokens``, the live sequences' context with this forward's
#: tokens, and ``kv_window_tokens``, those of them whose windowed rows are
#: resident (their ratio is what freeing saves: 1 = nothing was ever given
#: back); ``swa_pairs``, ``swa_atom_keys`` and ``full_atom_keys``
#: (``ragged.window_work``): what the atoms of ONE windowed and ONE full
#: layer cover. And, counted AFTER the forward, ``kv_window_blocks_freed``:
#: the window-pool blocks its sequences gave back as their next query moved
#: past them. Every other model's record has none of them.
WINDOW_FIELDS = ("kv_full_blocks_held", "kv_window_blocks_held",
                 "kv_live_ctx_tokens", "kv_window_tokens", "swa_pairs",
                 "swa_atom_keys", "full_atom_keys", "kv_window_blocks_freed")

#: what a phase is where nothing times the round: ``trace_stages`` off, or
#: an engine driven without a session
NO_PHASE = contextlib.nullcontext()

_SERVE_STAGE_SET = frozenset(SERVE_STAGES)
_FLEET_STAGE_SET = frozenset(FLEET_STAGES)
_ROUND_PHASE_SET = frozenset(ROUND_PHASES)


def check_stage(name: str, fleet: bool = False) -> str:
    """Validate a stage literal against the registry — the runtime twin of
    dslint's ``undeclared-stage-name`` rule (``mfu.region_scope`` pattern:
    a typo'd stage must fail loudly, not silently orphan its time)."""
    ok = name in (_FLEET_STAGE_SET if fleet else _SERVE_STAGE_SET)
    if not ok:
        kind = "fleet" if fleet else "serve"
        declared = FLEET_STAGES if fleet else SERVE_STAGES
        raise ValueError(f"undeclared {kind} stage {name!r}; declared: "
                         f"{declared} (monitor/reqtrace.py)")
    return name


def check_phase(name: str) -> str:
    """Validate a round-phase literal against :data:`ROUND_PHASES` (the
    :func:`check_stage` pattern: a typo'd phase must fail loudly, not open a
    bucket no reader sums)."""
    if name not in _ROUND_PHASE_SET:
        raise ValueError(f"undeclared round phase {name!r}; declared: "
                         f"{ROUND_PHASES} (monitor/reqtrace.py)")
    return name


# =========================================================================
# Stream loading (torn-tail salvage; the load_journal contract)
# =========================================================================


def load_stream(path: str) -> List[Dict[str, Any]]:
    """Parse one JSONL stream; a torn final line (crash mid-write) is
    skipped, not fatal — everything before it was flushed durably."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return []
    out: List[Dict[str, Any]] = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn tail
        if isinstance(rec, dict):
            out.append(rec)
    return out


_ATT_RE = re.compile(r"\.att([0-9.]+)\.jsonl$")


def file_attempt(path: str) -> str:
    """Generation/attempt suffix from a journal filename
    (``journal_rank0.att1.0.jsonl`` → ``"1.0"``; ``DSTPU_FLEET_GEN``
    namespaces the attempt — ``supervisor.journal_path``)."""
    m = _ATT_RE.search(os.path.basename(path))
    return m.group(1) if m else ""


def discover_root(root: str) -> Tuple[Dict[str, List[str]], List[str]]:
    """Fleet-root layout discovery: ``{replica_id: [journal files]}``
    (oldest incarnation first) plus the router stream files. Accepts a
    fleet root (``replica<id>/journal/``), a bare journal dir, or a dir
    of journals + ``router*.jsonl`` side by side."""
    replicas: Dict[str, List[str]] = {}
    if os.path.isdir(root):
        for sub in sorted(os.listdir(root)):
            jdir = os.path.join(root, sub, "journal")
            if sub.startswith("replica") and os.path.isdir(jdir):
                files = sorted(
                    _glob.glob(os.path.join(jdir, "journal_rank*.jsonl")),
                    key=lambda p: (os.path.getmtime(p), p))
                if files:
                    replicas[sub[len("replica"):]] = files
        if not replicas:
            files = sorted(
                _glob.glob(os.path.join(root, "journal_rank*.jsonl")),
                key=lambda p: (os.path.getmtime(p), p))
            if files:
                replicas["0"] = files
    router = sorted(_glob.glob(os.path.join(root, "router*.jsonl"))
                    ) if os.path.isdir(root) else []
    return replicas, router


# =========================================================================
# Join: streams → per-request span trees
# =========================================================================

#: Interval classifier: (previous edge, next edge) → stage. Every named
#: interval is a consecutive slice of the request's timeline, so the
#: per-stage self-times telescope to enqueue→close exactly — the 5%
#: reconciliation contract holds unless records are missing (torn tail),
#: and THAT shortfall is what ``unattributed`` reports.
_INTERVAL_STAGE = {
    ("route", "admit"): "transport",
    ("admit", "activate"): "queue_wait",
    ("admit", "emit"): "prefill",       # activation record lost (torn tail)
    ("admit", "close"): "queue_wait",   # closed while queued (shed/timeout)
    ("admit", "admit"): "replay",       # died before activation, replayed
    ("admit", "preempt"): "queue_wait",
    ("activate", "emit"): "prefill",
    ("activate", "close"): "prefill",
    ("activate", "preempt"): "prefill",
    ("activate", "admit"): "replay",
    ("emit", "emit"): "decode",
    ("emit", "preempt"): "decode",
    ("emit", "close"): "finalize",
    ("emit", "admit"): "replay",        # dead-replica gap → survivor admit
    ("preempt", "activate"): "requeue_wait",
    ("preempt", "close"): "requeue_wait",
    ("preempt", "admit"): "replay",
}


def _new_trace(uid: int) -> Dict[str, Any]:
    return {"uid": uid, "segments": [], "intervals": [], "stages": {},
            "t_route": None, "t_admit": None, "t_first_emit": None,
            "t_close": None, "ttft_s": None, "wall_s": None,
            "unattributed_s": 0.0, "reconciled_frac": None,
            "tokens": 0, "closes": 0, "close_reason": "", "outcome": "",
            "cached_prefix_len": None, "spool_wait_s": 0.0,
            "rounds": 0,
            "ttft_sla_s": None, "tenant": "", "verdicts": [],
            "replays": 0, "replica_path": []}


def join_traces(streams: Iterable[Tuple[str, str, Sequence[Dict[str, Any]]]],
                router_records: Sequence[Dict[str, Any]] = (),
                since: Optional[float] = None) -> Dict[int, Dict[str, Any]]:
    """Fuse router stream + per-replica journal streams into per-request
    span trees.

    ``streams`` is ``[(replica_id, attempt, records), ...]`` — what
    :func:`join_root` builds from disk, or what a caller hands over from
    in-memory ``trace_log`` buffers (``ServingSession.trace_log`` /
    ``FleetRouter.trace_log``). Records are ordered by wall ``t`` (the one
    clock every stream stamps) with append order breaking ties, so a
    replayed request's trace spans replicas and generations. ``since``
    drops requests whose first record predates it (per-load-point joins
    over an accumulating journal dir).
    """
    # (t, idx, kind, payload) per uid; router records first so same-t route
    # edges sort ahead of the replica admit they caused
    events: Dict[int, List[Tuple[float, int, str, Dict[str, Any]]]] = {}
    idx = 0

    def _push(uid: int, t: float, kind: str, payload: Dict[str, Any]) -> None:
        nonlocal idx
        if int(uid) < 0:
            # batch-scope stamps (round records, which fan out to their
            # ``uids``; the router's fleet-wide failover_claim) are not
            # requests
            return
        idx += 1
        events.setdefault(int(uid), []).append((float(t), idx, kind, payload))

    for rec in router_records:
        name = rec.get("name")
        data = rec.get("data") or {}
        t = float(rec.get("t", 0.0))
        uid = data.get("uid")
        if uid is None:
            continue
        if name == "fleet/route":
            _push(uid, t, "route", {"replica": data.get("replica", "")})
        elif name == "fleet/shed":
            _push(uid, t, "edge_shed", {"reason": data.get("reason", "")})
        elif name == "fleet/stage":
            _push(uid, t, "fleet_stage", dict(data))
        elif name == "fleet/failover":
            _push(uid, t, "failover", dict(data))
    for replica_id, attempt, records in streams:
        for rec in records:
            name = rec.get("name")
            data = rec.get("data") or {}
            t = float(rec.get("t", 0.0))
            uid = data.get("uid")
            if uid is None:
                continue
            if name == "serve/admit":
                n_prompt = data.get("n_tokens",
                                    len(data.get("tokens", []) or []))
                _push(uid, t, "admit", {
                    "replica": replica_id, "attempt": attempt,
                    "replayed": bool(data.get("replayed")),
                    "out_n": data.get("watermark",
                                      len(data.get("out", []) or [])),
                    "tenant": data.get("tenant", ""),
                    "ttft_sla_s": data.get("ttft_sla_s"),
                    "n_prompt": int(n_prompt)})
            elif name == "serve/emit":
                _push(uid, t, "emit",
                      {"n": int(data.get("n",
                                         len(data.get("tokens", []) or [])))})
            elif name == "serve/close":
                _push(uid, t, "close", {"reason": data.get("reason", "")})
            elif name == "serve/stage":
                stage = data.get("stage", "")
                if stage in ("round", "decode_round"):
                    # the round's record names the uids it sampled for
                    # (journals from before it carried them on decode_round)
                    for u in data.get("uids", ()):
                        _push(u, t, "round", {})
                elif stage in ("queue_wait", "requeue_wait"):
                    _push(uid, t, "activate", dict(data))
                elif stage == "preempt":
                    _push(uid, t, "preempt", dict(data))
                else:
                    _push(uid, t, "stage", dict(data))

    traces: Dict[int, Dict[str, Any]] = {}
    for uid, evs in events.items():
        evs.sort(key=lambda e: (e[0], e[1]))
        if since is not None and evs[0][0] < since:
            continue
        tr = _new_trace(uid)
        prev: Optional[Tuple[float, str]] = None  # last EDGE (t, kind)
        seg: Optional[Dict[str, Any]] = None
        for t, _i, kind, payload in evs:
            if kind == "round":
                tr["rounds"] += 1
                continue
            if kind == "stage":
                stage = payload.get("stage", "")
                if stage == "spool_wait":
                    tr["spool_wait_s"] += float(payload.get("dur", 0.0))
                elif stage == "gate":
                    tr["verdicts"].append(payload.get("verdict", ""))
                elif stage == "prefill":
                    if payload.get("cached_prefix_len") is not None:
                        tr["cached_prefix_len"] = int(
                            payload["cached_prefix_len"])
                continue
            if kind == "fleet_stage":
                stage = payload.get("stage", "")
                if stage == "placement":
                    tr["verdicts"].append("routed")
                elif stage == "edge_gate":
                    tr["verdicts"].append(payload.get("verdict", ""))
                continue
            if kind == "failover":
                if payload.get("outcome") in ("replayed", "dispatched"):
                    tr["replays"] += 1
                continue
            if kind == "edge_shed":
                tr["outcome"] = "edge_shed"
                tr["close_reason"] = f"edge_shed:{payload.get('reason', '')}"
                continue
            # ---- timeline edges -------------------------------------
            if kind == "route":
                # metadata edge: seeds t_route / the replica path and, at
                # stream start, the transport interval. A route stamp can
                # land AFTER the replica's admit (an in-process submit
                # returns before the router records the route) — it must
                # not reset ``prev`` mid-chain or the admit→activate→emit
                # intervals it would interrupt become unattributed.
                tr["t_route"] = t if tr["t_route"] is None else tr["t_route"]
                rep = payload.get("replica", "")
                if rep and (not tr["replica_path"]
                            or tr["replica_path"][-1] != rep):
                    tr["replica_path"].append(rep)
                if prev is None:
                    prev = (t, kind)
                continue
            if prev is not None:
                dt = max(0.0, t - prev[0])
                stage = _INTERVAL_STAGE.get((prev[1], kind), "unattributed")
                if dt > 0:
                    tr["intervals"].append((stage, prev[0], t))
            if kind == "admit":
                if tr["t_admit"] is None:
                    tr["t_admit"] = t
                    tr["tenant"] = payload.get("tenant", "")
                    tr["ttft_sla_s"] = payload.get("ttft_sla_s")
                seg = {"replica": payload.get("replica", ""),
                       "attempt": payload.get("attempt", ""),
                       "replayed": payload.get("replayed", False),
                       "watermark": payload.get("out_n", 0),
                       "t_admit": t, "t_first_emit": None,
                       "t_last": t, "closed": False, "tokens": 0}
                tr["segments"].append(seg)
                if payload.get("replica") and (
                        not tr["replica_path"]
                        or tr["replica_path"][-1] != payload["replica"]):
                    tr["replica_path"].append(payload["replica"])
            elif kind == "activate":
                if seg is not None:
                    seg["t_last"] = t
                if payload.get("cached_prefix_len") is not None \
                        and tr["cached_prefix_len"] is None:
                    tr["cached_prefix_len"] = int(payload["cached_prefix_len"])
            elif kind == "emit":
                if tr["t_first_emit"] is None:
                    tr["t_first_emit"] = t
                tr["tokens"] += payload.get("n", 0)
                if seg is not None:
                    if seg["t_first_emit"] is None:
                        seg["t_first_emit"] = t
                    seg["t_last"] = t
                    seg["tokens"] += payload.get("n", 0)
            elif kind == "preempt":
                if seg is not None:
                    seg["t_last"] = t
            elif kind == "close":
                tr["closes"] += 1
                tr["t_close"] = t
                tr["close_reason"] = payload.get("reason", "")
                if seg is not None:
                    seg["closed"] = True
                    seg["t_last"] = t
            prev = (t, kind)
        # ---- derived summary ----------------------------------------
        if tr["t_admit"] is not None and tr["t_first_emit"] is not None \
                and tr["segments"] and not tr["segments"][0]["replayed"]:
            tr["ttft_s"] = tr["t_first_emit"] - tr["t_admit"]
        if tr["t_admit"] is not None and tr["t_close"] is not None:
            tr["wall_s"] = max(0.0, tr["t_close"] - tr["t_admit"])
            for stage, t0, t1 in tr["intervals"]:
                if t0 >= tr["t_admit"]:  # transport precedes enqueue
                    tr["stages"][stage] = (tr["stages"].get(stage, 0.0)
                                           + (t1 - t0))
            attributed = sum(v for s, v in tr["stages"].items()
                             if s != "unattributed")
            tr["unattributed_s"] = max(0.0, tr["wall_s"] - attributed)
            tr["reconciled_frac"] = (1.0 if tr["wall_s"] <= 0 else
                                     min(1.0, attributed / tr["wall_s"]))
        if not tr["outcome"]:
            reason = tr["close_reason"]
            tr["outcome"] = ("open" if tr["closes"] == 0 else
                             "shed" if reason.startswith("shed")
                             or reason == "replay_shed" else "closed")
        traces[uid] = tr
    return traces


def load_root(root: str) -> Tuple[List[Tuple[str, str, List[Dict[str, Any]]]],
                                  List[Dict[str, Any]]]:
    """``(streams, router_records)`` of a fleet root (or bare journal dir),
    in the shapes :func:`join_traces` and :func:`round_phases` take."""
    replicas, router_files = discover_root(root)
    router_records: List[Dict[str, Any]] = []
    for path in router_files:
        router_records.extend(load_stream(path))
    streams = [(rid, file_attempt(path), load_stream(path))
               for rid, files in sorted(replicas.items()) for path in files]
    return streams, router_records


def join_root(root: str, since: Optional[float] = None
              ) -> Dict[int, Dict[str, Any]]:
    """Disk entry point: discover + load + join a fleet root (or bare
    journal dir)."""
    streams, router_records = load_root(root)
    return join_traces(streams, router_records, since=since)


def round_phases(streams: Iterable[Tuple[str, str, Sequence[Dict[str, Any]]]]
                 ) -> Optional[Dict[str, Any]]:
    """Where the host's time goes inside a scheduling round, from the
    ``round`` stage records of ``streams``: per :data:`ROUND_PHASES` phase
    the p50/p99/mean seconds a round spent in it, the same for the whole
    round and for how far into it the forward was launched (``launch_s``),
    and per program launched the number of rounds and the mean of what its
    forward covered (:data:`FORWARD_FIELDS`); ``loop`` (:data:`LOOP_FIELDS`)
    where the records are a looped stack's. ``None`` when the streams
    hold no such record (journals from before it, or ``trace_stages``
    off)."""
    rounds: List[Dict[str, Any]] = []
    for _rid, _att, records in streams:
        own = [rec["data"] for rec in records
               if rec.get("name") == "serve/stage"
               and (rec.get("data") or {}).get("stage") == "round"]
        # what the device counts reaches the host a round late
        # (MOE_TAIL_FIELDS): give each record the counts of the forward it
        # launched, its successor's
        rounds += [{**d, **{f: nxt.get(f, 0) for f in MOE_TAIL_FIELDS
                            if f in d or f in nxt}}
                   for d, nxt in zip(own, own[1:] + [{}])]
    if not rounds:
        return None

    def _summary(vals: List[float]) -> Dict[str, float]:
        return {"p50": _rank_quantile(vals, 0.5),
                "p99": _rank_quantile(vals, 0.99),
                "mean_s": sum(vals) / len(vals)}

    # a field only some programs write (moe_rows) is reported where written
    fields = FORWARD_FIELDS + tuple(
        f for f in MOE_TAIL_FIELDS + MOE_STATIC_FIELDS + DSA_FIELDS
        if f not in FORWARD_FIELDS and any(f in d for d in rounds))
    by_program: Dict[str, List[Dict[str, Any]]] = {}
    for d in rounds:
        by_program.setdefault(d.get("program") or "(nothing launched)",
                              []).append(d)
    launched = [d["launch_t"] - d["t0"] for d in rounds
                if d.get("launch_t") is not None]
    # a looped stack: its passes, and the exit counter where it stood last
    loop = {f: next((d[f] for d in reversed(rounds) if f in d), None)
            for f in LOOP_FIELDS}
    return {"rounds": len(rounds),
            **({"loop": loop} if loop["passes"] else {}),
            "round_s": _summary([d["t1"] - d["t0"] for d in rounds]),
            "launch_s": _summary(launched) if launched else None,
            "programs": {name: {"rounds": len(ds), **{
                f: sum(d.get(f, 0) for d in ds) / len(ds) for f in fields}}
                for name, ds in by_program.items()},
            "phases": {p: _summary([float((d.get("phases") or {}).get(p, 0.0))
                                    for d in rounds])
                       for p in ROUND_PHASES}}


# =========================================================================
# Attribution: traces → TTFT/ITL waterfall, tail, SLO burn, exemplars
# =========================================================================


def _rank_quantile(vals: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile (exact, no interpolation — these are offline
    joins over full populations, not streaming buckets)."""
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def _quantiles(vals: Sequence[float]) -> Dict[str, Optional[float]]:
    return {f"p{int(q * 100)}": _rank_quantile(vals, q)
            for q in (0.5, 0.95, 0.99)}


def _clip_stages(tr: Dict[str, Any], t0: float, t1: float
                 ) -> Dict[str, float]:
    """Per-stage seconds inside the window [t0, t1] (interval clipping)."""
    out: Dict[str, float] = {}
    for stage, a, b in tr["intervals"]:
        lo, hi = max(a, t0), min(b, t1)
        if hi > lo:
            out[stage] = out.get(stage, 0.0) + (hi - lo)
    return out


def slo_burn_windows(traces: Dict[int, Dict[str, Any]],
                     window_s: float = 60.0, budget: float = 0.05
                     ) -> List[Dict[str, Any]]:
    """TTFT-SLO burn rate over fixed sliding windows: per window the
    fraction of first tokens that missed their per-request SLA, divided by
    the error budget (burn > 1 ⇒ the budget is being spent faster than it
    accrues — the standard multi-window burn-rate alerting input)."""
    samples = [(tr["t_first_emit"],
                tr["ttft_s"] is not None and tr["ttft_sla_s"] is not None
                and tr["ttft_s"] <= tr["ttft_sla_s"])
               for tr in traces.values()
               if tr["t_first_emit"] is not None
               and tr["ttft_sla_s"] is not None and tr["ttft_s"] is not None]
    if not samples:
        return []
    samples.sort()
    t_lo, t_hi = samples[0][0], samples[-1][0]
    out: List[Dict[str, Any]] = []
    t = t_lo
    while t <= t_hi:
        inside = [ok for ts, ok in samples if t <= ts < t + window_s]
        if inside:
            miss = 1.0 - sum(inside) / len(inside)
            out.append({"t0": t, "n": len(inside),
                        "miss_frac": round(miss, 4),
                        "burn": round(miss / max(budget, 1e-9), 3)})
        t += window_s
    return out


def attribution(traces: Dict[int, Dict[str, Any]], worst_n: int = 5,
                slo_window_s: float = 60.0, slo_budget: float = 0.05
                ) -> Dict[str, Any]:
    """The request waterfall: stage-decomposed TTFT/ITL quantiles, tail
    attribution, reconciliation summary, SLO burn and worst-request
    exemplars — the ``detail.request_waterfall`` payload."""
    done = [tr for tr in traces.values()
            if tr["t_admit"] is not None and tr["t_close"] is not None]
    firsts = [tr for tr in done if tr["ttft_s"] is not None]
    out: Dict[str, Any] = {
        "requests": len(traces), "closed": len(done),
        "edge_sheds": sum(1 for tr in traces.values()
                          if tr["outcome"] == "edge_shed"),
        "multi_close": sum(1 for tr in traces.values() if tr["closes"] > 1),
        "failover_spans": sum(1 for tr in done if tr["replays"] > 0
                              or len({s["replica"]
                                      for s in tr["segments"]}) > 1),
    }
    recon = [tr["reconciled_frac"] for tr in done
             if tr["reconciled_frac"] is not None]
    out["reconciliation"] = {
        "median_frac": _rank_quantile(recon, 0.5),
        "min_frac": min(recon) if recon else None,
        "within_5pct_frac": (round(sum(1 for f in recon if f >= 0.95)
                                   / len(recon), 4) if recon else None)}
    # ---- TTFT decomposition --------------------------------------------
    stage_ttft: Dict[str, List[float]] = {}
    for tr in firsts:
        clipped = _clip_stages(tr, tr["t_admit"], tr["t_first_emit"])
        for stage in set(clipped) | set(stage_ttft):
            stage_ttft.setdefault(stage, []).append(clipped.get(stage, 0.0))
    # equal-length arrays (zeros for requests lacking a stage) so quantile
    # ranks align across stages
    n_first = len(firsts)
    for stage, vals in stage_ttft.items():
        vals.extend(0.0 for _ in range(n_first - len(vals)))
    out["ttft"] = _quantiles([tr["ttft_s"] for tr in firsts])
    out["ttft_by_stage"] = {
        stage: {**_quantiles(vals),
                "mean_s": round(sum(vals) / len(vals), 6) if vals else 0.0}
        for stage, vals in sorted(stage_ttft.items())}
    means = {s: v["mean_s"] for s, v in out["ttft_by_stage"].items()}
    out["dominant_ttft_stage"] = (max(means, key=means.get)
                                  if means else None)
    # ---- ITL decomposition (per emitted token past the first) ----------
    stage_itl: Dict[str, List[float]] = {}
    decoders = [tr for tr in done if tr["t_first_emit"] is not None
                and tr["tokens"] > 1]
    for tr in decoders:
        clipped = _clip_stages(tr, tr["t_first_emit"], tr["t_close"])
        denom = max(1, tr["tokens"] - 1)
        for stage in set(clipped) | set(stage_itl):
            stage_itl.setdefault(stage, []).append(
                clipped.get(stage, 0.0) / denom)
    n_dec = len(decoders)
    for stage, vals in stage_itl.items():
        vals.extend(0.0 for _ in range(n_dec - len(vals)))
    out["itl_by_stage"] = {
        stage: {**_quantiles(vals),
                "mean_s": round(sum(vals) / len(vals), 6) if vals else 0.0}
        for stage, vals in sorted(stage_itl.items())}
    # ---- tail attribution: slowest TTFT decile vs the median cohort ----
    if len(firsts) >= 4:
        ranked = sorted(firsts, key=lambda tr: tr["ttft_s"])
        n = len(ranked)
        tail = ranked[max(0, n - max(1, n // 10)):]
        mid = ranked[n // 4: max(n // 4 + 1, 3 * n // 4)]

        def _mean_stages(group):
            acc: Dict[str, float] = {}
            for tr in group:
                for stage, v in _clip_stages(
                        tr, tr["t_admit"], tr["t_first_emit"]).items():
                    acc[stage] = acc.get(stage, 0.0) + v
            return {s: v / len(group) for s, v in acc.items()}

        tail_m, mid_m = _mean_stages(tail), _mean_stages(mid)
        by_stage = {
            stage: {"median_s": round(mid_m.get(stage, 0.0), 6),
                    "tail_s": round(tail_m.get(stage, 0.0), 6),
                    "growth_s": round(tail_m.get(stage, 0.0)
                                      - mid_m.get(stage, 0.0), 6)}
            for stage in sorted(set(tail_m) | set(mid_m))}
        growth = {s: v["growth_s"] for s, v in by_stage.items()}
        out["tail"] = {
            "tail_n": len(tail), "median_n": len(mid),
            "by_stage": by_stage,
            "dominant_stage": (max(growth, key=growth.get)
                               if growth else None)}
    else:
        out["tail"] = None
    # ---- decode rounds + prefix visibility -----------------------------
    out["decode_rounds"] = sum(tr["rounds"] for tr in done)
    cached = [tr["cached_prefix_len"] for tr in done
              if tr["cached_prefix_len"] is not None]
    out["cached_prefix_tokens_mean"] = (
        round(sum(cached) / len(cached), 2) if cached else None)
    # ---- SLO burn ------------------------------------------------------
    burn = slo_burn_windows(traces, window_s=slo_window_s, budget=slo_budget)
    out["slo_burn"] = {
        "window_s": slo_window_s, "budget": slo_budget,
        "windows": burn,
        "max_burn": max((w["burn"] for w in burn), default=None)}
    # ---- worst-request exemplar waterfalls -----------------------------
    ranked = sorted(firsts, key=lambda tr: -(tr["ttft_s"] or 0.0))
    out["worst"] = [
        {"uid": tr["uid"], "ttft_s": round(tr["ttft_s"], 6),
         "wall_s": round(tr["wall_s"], 6) if tr["wall_s"] is not None
         else None,
         "tokens": tr["tokens"], "close_reason": tr["close_reason"],
         "replays": tr["replays"],
         "replica_path": tr["replica_path"],
         "cached_prefix_len": tr["cached_prefix_len"],
         "unattributed_s": round(tr["unattributed_s"], 6),
         "stages": {s: round(v, 6) for s, v in sorted(tr["stages"].items())}}
        for tr in ranked[:worst_n]]
    return out


def waterfall(streams: Iterable[Tuple[str, str, Sequence[Dict[str, Any]]]],
              router_records: Sequence[Dict[str, Any]] = (),
              since: Optional[float] = None, **kw) -> Dict[str, Any]:
    """join + attribution in one call: hand over the in-memory
    ``trace_log`` buffers, get the payload :func:`attribution` returns."""
    return attribution(join_traces(streams, router_records, since=since),
                       **kw)
