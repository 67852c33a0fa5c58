#!/usr/bin/env python
"""Offline flight-recorder report: step timeline, goodput split, stragglers.

Renders the rank-local JSONL stream written by the telemetry layer
(``deepspeedsyclsupport_tpu/monitor/telemetry.py`` flight recorder +
``monitor/monitor.py::JsonlMonitor``) into the summary an operator wants
after a preemption storm — no devices, no jax session, safe on a login node.

Usage::

    python tools/trace_report.py telemetry_logs/flightrec_rank0.jsonl
    python tools/trace_report.py telemetry_logs/            # whole directory
    python tools/trace_report.py 'logs/flightrec_rank*.jsonl' --last 30
    python tools/trace_report.py telemetry_logs/ --pod      # pod-scope view
    python tools/trace_report.py fleet_root/ --fleet        # fleet view
    python tools/trace_report.py fleet_root/ --requests     # request waterfall
    python tools/trace_report.py telemetry_logs/flightrec_rank0.jsonl --setup

Inputs may be directories (their ``flightrec*.jsonl``), glob patterns, or
explicit files; rank ids are inferred from the ``rank<N>`` filename
convention (or each stream's meta record). With several rank files the
report adds a straggler section comparing each host's accumulated step
wall-clock (the SPMD analog of per-rank collective latency — a host far
above the minimum is the straggler). ``--pod`` switches to the full
pod-scope report (``tools/pod_report.py``): clock-aligned per-step skew,
straggler ledger and the per-traffic-class bandwidth decomposition.

``--setup`` reads the set-up ledger (``monitor/telemetry.py``: what every
program cost to trace, lower and compile or load, whether the persistent
cache held it, the set-up spans and what the engines decided) out of a
flight-recorder JSONL (its last ``setup/ledger`` record) or out of a JSON
file a live process wrote (``json.dump(telemetry.setup_ledger(), f)``): the
operator's reading of a slow cold start.

Exit code 0 on success, 2 when no input file yields any records.
"""
import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

# one loader for monitor/pod.py lives in pod_report (by file path, NOT
# through the package — the package __init__ imports jax and this tool's
# contract is "safe on a login node", stdlib only)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pod_report  # noqa: E402

_pod = pod_report.pod


def _load_monitor_module(name: str):
    """Load ``monitor/<name>.py`` by file path, NOT through the package
    (same login-node contract as the pod.py loader above: the package
    __init__ imports jax; ``reqtrace`` and ``setup_folds`` are deliberately
    stdlib-only)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "deepspeedsyclsupport_tpu", "monitor",
        name + ".py")
    spec = importlib.util.spec_from_file_location("_dstpu_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

def setup_report(path: str) -> Optional[str]:
    """The set-up ledger in ``path`` rendered (``setup_folds.render``): one
    line a program, the span tree with self times, the decisions. ``path``
    is a flight-recorder JSONL (the last ``setup/ledger`` record a dump
    wrote) or a JSON file holding the records themselves, as a list or as
    ``{"records": [...], "dropped": n}``. None where it holds no ledger."""
    records, dropped = None, 0
    try:
        with open(path) as f:
            doc = json.load(f)
        records = doc["records"] if isinstance(doc, dict) else doc
        dropped = doc.get("dropped", 0) if isinstance(doc, dict) else 0
    except (OSError, ValueError, KeyError, TypeError):
        dumps = [r for r in load_records(path)
                 if r.get("name") == "setup/ledger"]
        if dumps:
            data = dumps[-1].get("data") or {}
            records, dropped = data.get("records"), data.get("dropped", 0)
    if not records:
        return None
    return "\n".join(_load_monitor_module("setup_folds").render(
        records, dropped=dropped))


#: A goodput split must account for at least this fraction of wall-clock —
#: the accounter computes ``other`` as the residual, so anything below this
#: indicates a truncated/corrupt log rather than rounding.
ACCOUNTING_FLOOR = 0.99


def load_records(path: str) -> List[Dict[str, Any]]:
    """Parse one JSONL file with truncation salvage (``monitor/pod.py``): a
    torn final line is EXPECTED for a crash dump — everything before it is
    still good and is kept."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        print(f"error: cannot read {path}: {e}", file=sys.stderr)
        return []
    records, bad, truncated = _pod.parse_stream_text(text)
    if bad:
        print(f"  note: {path}: {bad} torn/unparsable line(s) skipped",
              file=sys.stderr)
    elif truncated:
        print(f"  note: {path}: no trailing newline — stream truncated "
              f"mid-write", file=sys.stderr)
    return records


def _fmt_s(sec: float) -> str:
    return f"{sec * 1000:.1f}ms" if sec < 1.0 else f"{sec:.2f}s"


def step_timeline(records: List[Dict[str, Any]], last: int) -> List[str]:
    steps = [r for r in records
             if r.get("kind") == "span" and r.get("name") == "step"]
    lines = [f"step timeline (last {min(last, len(steps))} of {len(steps)} "
             f"recorded steps)",
             f"{'step':>8}{'duration':>12}{'compiles':>10}  notes"]
    for r in steps[-last:]:
        data = r.get("data") or {}
        notes = ""
        if data.get("compiles"):
            notes = (f"recompile x{data['compiles']} "
                     f"({_fmt_s(data.get('compile_s', 0.0))})")
        lines.append(f"{r.get('step', '?'):>8}{_fmt_s(r.get('dur', 0.0)):>12}"
                     f"{data.get('compiles', 0):>10}  {notes}")
    if not steps:
        lines.append("  (no step spans recorded)")
    return lines


def goodput_summary(records: List[Dict[str, Any]]) -> List[str]:
    summaries = [r for r in records if r.get("kind") == "goodput"]
    lines = ["goodput"]
    if not summaries:
        lines.append("  (no goodput summary — telemetry.goodput disabled or "
                     "log truncated before the first dump)")
        return lines
    s = summaries[-1].get("data") or {}
    total = float(s.get("total", 0.0)) or 1e-9
    cats = [k for k in ("productive", "checkpoint", "compile",
                        "offload_stall", "rollback", "startup", "other")
            if k in s]
    accounted = sum(float(s[c]) for c in cats)
    for c in cats:
        v = float(s[c])
        lines.append(f"  {c:<12}{_fmt_s(v):>12}  {100.0 * v / total:6.2f}%")
    lines.append(f"  {'total':<12}{_fmt_s(total):>12}")
    frac = accounted / total
    lines.append(f"  accounted: {100.0 * frac:.2f}% of wall-clock"
                 + ("" if frac >= ACCOUNTING_FLOOR else
                    f"  <-- BELOW {ACCOUNTING_FLOOR:.0%}: log is truncated "
                    f"or the accounter is broken"))
    lines.append(f"  productive fraction: "
                 f"{100.0 * float(s.get('productive_frac', 0.0)):.2f}%")
    return lines


def events_summary(records: List[Dict[str, Any]]) -> List[str]:
    lines = ["notable events"]
    compiles = [r for r in records if r.get("kind") == "event"
                and r.get("name") == "compile/train_step"]
    for r in compiles[-5:]:
        data = r.get("data") or {}
        diff = data.get("shape_diff", {})
        what = ("initial compile" if diff.get("initial")
                else f"shape diff: {json.dumps(diff)[:120]}")
        # the duration adds the three phases; a newer stream has them apart
        phases = ", ".join(f"{p} {_fmt_s(data[p + '_s'])}"
                           for p in ("trace", "lower", "compile")
                           if p + "_s" in data)
        lines.append(f"  step {r.get('step', '?')}: recompile "
                     f"({_fmt_s(r.get('dur', 0.0))}"
                     f"{': ' + phases if phases else ''}) — {what}")
    dumps = [r for r in records if r.get("kind") == "dump"]
    for r in dumps:
        reason = (r.get("data") or {}).get("reason", "?")
        lines.append(f"  dump: reason={reason}")
        res = (r.get("data") or {}).get("resilience", {})
        nonzero = {k: v for k, v in res.items() if v}
        if nonzero:
            lines.append(f"    resilience counters: {nonzero}")
    mems = [r for r in records if r.get("kind") == "gauge"
            and r.get("name") == "memory/hbm"]
    if mems:
        peak = max(int((r.get("data") or {}).get("peak_bytes_in_use", 0))
                   for r in mems)
        lines.append(f"  peak HBM: {peak / 2**30:.2f} GiB")
    metrics: Dict[str, Any] = {}
    for r in records:
        if r.get("kind") == "metric":
            metrics[r["name"]] = r.get("value")
    if metrics:
        lines.append("  last metric values:")
        for name in sorted(metrics):
            lines.append(f"    {name} = {metrics[name]}")
    if len(lines) == 1:
        lines.append("  (none)")
    return lines


def offload_summary(records: List[Dict[str, Any]]) -> List[str]:
    """Hierarchical-offload view from ``offload/step`` records
    (``runtime/offload_pipeline.py`` ``OffloadStats`` shape): bytes and
    effective GB/s per direction, host fp32-Adam seconds, exposed stall,
    and overlap efficiency (1 − exposed/total transfer time)."""
    steps = [r.get("data") or {} for r in records
             if r.get("kind") == "event" and r.get("name") == "offload/step"]
    if not steps:
        return []
    tot: Dict[str, float] = {}
    for d in steps:
        for k, v in d.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                tot[k] = tot.get(k, 0.0) + float(v)
    lines = [f"offload pipeline ({len(steps)} offloaded step(s), "
             f"{int(tot.get('n_buckets', 0) / max(1, len(steps)))} "
             f"bucket(s)/step)"]
    for direction, label in (("d2h", "D2H grad pull"),
                             ("h2d", "H2D master push"),
                             ("nvme_read", "NVMe moment read"),
                             ("nvme_write", "NVMe moment write")):
        nbytes = tot.get(f"{direction}_bytes", 0.0)
        if not nbytes:
            continue
        secs = tot.get(f"{direction}_s", 0.0)
        gbps = f"{nbytes / 1e9 / secs:7.2f} GB/s" if secs > 0 else \
            "    (async)"
        lines.append(f"  {label:<18}{nbytes / 2**20:10.1f} MiB  {gbps}")
    lines.append(f"  host compute      {_fmt_s(tot.get('host_compute_s', 0.0)):>10}")
    lines.append(f"  exposed stall     {_fmt_s(tot.get('stall_s', 0.0)):>10}")
    transfer = tot.get("transfer_s", 0.0)
    if transfer > 0:
        eff = min(1.0, max(0.0, 1.0 - tot.get("stall_s", 0.0) / transfer))
        lines.append(f"  overlap efficiency {eff:8.2f}  (1 - exposed/total "
                     f"transfer)")
    hwm = max((float(d.get("window_hwm_bytes", 0) or 0) for d in steps),
              default=0.0)
    if hwm:
        lines.append(f"  moment-window high-water {hwm / 2**20:8.1f} MiB")
    return lines


def serve_recovery_summary(records: List[Dict[str, Any]]) -> List[str]:
    """``Serve/recovery.*`` view: journal lifecycle counts (a request
    journal IS a flight-recorder stream, so this tool reads it directly),
    stuck-decode watchdog arms/hangs, and the recovery counters +
    time-to-recover quantiles from metric records / dump snapshots."""
    admits = [r for r in records if r.get("name") == "serve/admit"]
    if not admits and not any(
            str(r.get("name", "")).startswith(
                "Serve/recovery.")  # dslint: allow(undeclared-event-name) read-side filter
            for r in records) and not any(
            r.get("kind") == "dump" and any(
                k.startswith("Serve/recovery.")  # dslint: allow(undeclared-event-name) read-side filter
                for k in ((r.get("data") or {}).get("metrics", {})
                          .get("counters", {})))
            for r in records):
        return []
    lines = ["serving recovery (Serve/recovery.* + request journal)"]
    if admits:
        uids = {(r.get("data") or {}).get("uid") for r in admits}
        replayed = {(r.get("data") or {}).get("uid") for r in admits
                    if (r.get("data") or {}).get("replayed")}
        closed = {(r.get("data") or {}).get("uid"): (r.get("data") or {})
                  .get("reason", "?") for r in records
                  if r.get("name") == "serve/close"}
        emitted = sum(len((r.get("data") or {}).get("tokens", []))
                      for r in records if r.get("name") == "serve/emit")
        lines.append(f"  journal: {len(uids)} request(s), "
                     f"{len(replayed)} replayed admit(s), "
                     f"{len(closed)} closed, "
                     f"{len(uids) - len(closed)} in flight, "
                     f"{emitted} token(s) emitted")
        reasons: Dict[str, int] = {}
        for reason in closed.values():
            reasons[reason] = reasons.get(reason, 0) + 1
        if reasons:
            lines.append(f"  close reasons: "
                         + ", ".join(f"{k}={v}"
                                     for k, v in sorted(reasons.items())))
    hangs = [r for r in records if r.get("name") == "serve/hang"]
    for r in hangs:
        d = r.get("data") or {}
        lines.append(f"  stuck-decode hang: round {r.get('step', '?')} "
                     f"waited {d.get('waited_s', '?')}s > deadline "
                     f"{d.get('deadline_s', '?')}s (rc 219)")
    # latest scalar values: metric records win; else the last dump marker's
    # registry snapshot
    latest: Dict[str, Any] = {}
    hist = None
    for r in records:
        if r.get("kind") == "metric" and \
                str(r.get("name", "")).startswith(
                    "Serve/recovery."):  # dslint: allow(undeclared-event-name) read-side filter
            latest[r["name"]] = r.get("value")
        if r.get("kind") == "dump":
            metrics = (r.get("data") or {}).get("metrics", {})
            for k, v in metrics.get("counters", {}).items():
                if k.startswith("Serve/recovery."):  # dslint: allow(undeclared-event-name) read-side filter
                    latest[k] = v
            h = metrics.get("histograms", {}).get(
                "Serve/recovery.time_to_recover_s")
            if h and h.get("count"):
                hist = h
    for name in sorted(latest):
        lines.append(f"  {name} = {latest[name]}")
    if hist:
        qs = {q: _pod.histogram_quantile(tuple(hist["buckets"]),
                                         hist["counts"], hist["count"], q)
              for q in (0.5, 0.95, 0.99)}
        qtxt = ", ".join(f"p{int(q * 100)}={v:.3f}s"
                         for q, v in qs.items() if v is not None)
        lines.append(f"  time_to_recover ({hist['count']} sample(s)): "
                     f"{qtxt}")
    return lines


def serve_prefix_summary(records: List[Dict[str, Any]]) -> List[str]:
    """``Serve/prefix.*`` view: cross-request KV prefix-cache reuse.
    Latest scalar values come from metric records, falling back to the last
    dump marker's registry snapshot; the hit ratio is recomputed from the
    final hit/miss totals so it reflects the whole stream, not the last
    flush window."""
    latest: Dict[str, Any] = {}
    for r in records:
        name = str(r.get("name", ""))
        if r.get("kind") == "metric" and name.startswith(
                "Serve/prefix."):  # dslint: allow(undeclared-event-name) read-side filter
            latest[name] = latest.get(name, 0) + r.get("value", 0) \
                if name.rsplit(".", 1)[-1] not in ("hit_ratio",
                                                   "pinned_blocks") \
                else r.get("value")
        if r.get("kind") == "dump":
            metrics = (r.get("data") or {}).get("metrics", {})
            for section in ("counters", "gauges"):
                for k, v in metrics.get(section, {}).items():
                    if k.startswith("Serve/prefix."):  # dslint: allow(undeclared-event-name) read-side filter
                        latest[k] = v
    if not latest:
        return []
    lines = ["prefix reuse (Serve/prefix.*)"]
    hits = float(latest.get("Serve/prefix.hits", 0) or 0)
    misses = float(latest.get("Serve/prefix.misses", 0) or 0)
    if hits + misses > 0:
        lines.append(f"  hit ratio: {hits / (hits + misses):.3f} "
                     f"({int(hits)} hit(s) / {int(hits + misses)} lookup(s))")
    for name in sorted(latest):
        lines.append(f"  {name} = {latest[name]}")
    return lines


def health_summary(records: List[Dict[str, Any]]) -> List[str]:
    """Training-health view from ``health/step`` records
    (``runtime/sentinel.py`` verdict shape via ``Telemetry.record_health``):
    ladder action counts by cause, the skipped data-stream positions a
    resumed run must replay identically, rollback targets, and the last
    observed robust z-scores. Empty list when the sentinel never spoke."""
    health = [r for r in records if r.get("kind") == "event"
              and r.get("name") == "health/step"]
    if not health:
        return []
    lines = ["training health (sentinel ladder)"]
    actions: Dict[str, int] = {}
    causes: Dict[str, int] = {}
    skipped: List[Any] = []
    last: Dict[str, Any] = {}
    for r in health:
        d = r.get("data") or {}
        a = d.get("action", "?")
        actions[a] = actions.get(a, 0) + 1
        if d.get("cause"):
            causes[d["cause"]] = causes.get(d["cause"], 0) + 1
        if d.get("skipped") and d.get("position") is not None:
            skipped.append(d["position"])
        for k in ("loss_z", "grad_norm_z", "nonfinite", "streak"):
            if d.get(k) is not None:
                last[k] = d[k]
    lines.append("  actions: " + ", ".join(
        f"{k}={v}" for k, v in sorted(actions.items())))
    if causes:
        lines.append("  causes:  " + ", ".join(
            f"{k}={v}" for k, v in sorted(causes.items())))
    if skipped:
        shown = ", ".join(str(p) for p in skipped[:16])
        more = "" if len(skipped) <= 16 else f" (+{len(skipped) - 16} more)"
        lines.append(f"  skipped positions: {shown}{more}")
    for r in health:
        d = r.get("data") or {}
        if d.get("action") == "rollback":
            lines.append(f"  rollback at step {r.get('step', '?')}: "
                         f"-> step {d.get('rolled_back_to', '?')} "
                         f"(tag {d.get('tag', '?')}, "
                         f"{d.get('duration_s', 0.0):.2f}s)")
        elif d.get("action") == "abort":
            lines.append(f"  ABORT at step {r.get('step', '?')}: "
                         f"cause={d.get('cause', '?')} -> rc 220")
    if last:
        lines.append("  last observed: " + ", ".join(
            f"{k}={last[k]}" for k in sorted(last)))
    return lines


def _simple_quantiles(values: List[float],
                      qs=(0.5, 0.95, 0.99)) -> Dict[float, float]:
    """Nearest-rank quantiles over raw samples (stdlib; the fleet view has
    the individual TTFTs, no bucketed histogram needed)."""
    if not values:
        return {}
    s = sorted(values)
    return {q: s[min(len(s) - 1, max(0, round(q * (len(s) - 1))))]
            for q in qs}


def discover_fleet(root: str):
    """A fleet root (``inference/v2/fleet``) holds one ``replica<i>/``
    subdir per replica (journals under ``journal/`` or flat) plus the
    router's ``router*.jsonl`` stream. Returns
    ``(replicas: {id: (journal_dir, [files])}, router_files)``."""
    import glob as _glob

    replicas: Dict[str, Any] = {}
    for sub in sorted(_glob.glob(os.path.join(root, "replica*"))):
        if not os.path.isdir(sub):
            continue
        rid = os.path.basename(sub)[len("replica"):] or sub
        jdir = os.path.join(sub, "journal")
        if not os.path.isdir(jdir):
            jdir = sub
        files = sorted(_glob.glob(os.path.join(jdir, "journal_rank*.jsonl")),
                       key=lambda p: (os.path.getmtime(p), p))
        if files:
            replicas[rid] = (jdir, files)
    router_files = sorted(_glob.glob(os.path.join(root, "router*.jsonl")))
    return replicas, router_files


def fleet_summary(root: str) -> Optional[str]:
    """Merged cross-replica fleet view: per-replica journal lifecycle
    counts, fleet-level closure (exactly-once check included), failover
    accounting from the router stream + claim files, and routed-TTFT
    quantiles joined route-record → first-emit across processes."""
    replicas, router_files = discover_fleet(root)
    if not replicas and not router_files:
        return None
    lines = [f"fleet report — {len(replicas)} replica(s), router stream: "
             f"{'yes' if router_files else 'no'}"]
    all_uids: set = set()
    closed_uids: set = set()
    close_counts: Dict[Any, int] = {}
    first_emit_t: Dict[Any, float] = {}
    total_tokens = 0
    claims = 0
    for rid, (jdir, files) in sorted(replicas.items()):
        admits: set = set()
        replayed: set = set()
        closes: Dict[Any, str] = {}
        tokens = 0
        prefix: Dict[str, Any] = {}
        recover_hist = None
        for path in files:
            for rec in load_records(path):
                name = rec.get("name")
                data = rec.get("data") or {}
                if rec.get("kind") == "dump":
                    # the dump marker carries the replica's final registry
                    # snapshot: per-replica prefix reuse + recovery quantiles
                    m = (rec.get("data") or {}).get("metrics", {})
                    for section in ("counters", "gauges"):
                        for k, v in m.get(section, {}).items():
                            if k.startswith("Serve/prefix."):  # dslint: allow(undeclared-event-name) read-side filter
                                prefix[k] = v
                    h = m.get("histograms", {}).get(
                        "Serve/recovery.time_to_recover_s")
                    if h and h.get("count"):
                        recover_hist = h
                    continue
                uid = data.get("uid")
                if uid is None:
                    continue
                if name == "serve/admit":
                    admits.add(uid)
                    if data.get("replayed"):
                        replayed.add(uid)
                elif name == "serve/emit":
                    tokens += len(data.get("tokens", []))
                    t = rec.get("t")
                    if t is not None and uid not in first_emit_t:
                        first_emit_t[uid] = float(t)
                elif name == "serve/close":
                    closes[uid] = data.get("reason", "?")
                    close_counts[uid] = close_counts.get(uid, 0) + 1
        all_uids |= admits
        closed_uids |= set(closes)
        total_tokens += tokens
        reasons: Dict[str, int] = {}
        for reason in closes.values():
            reasons[reason] = reasons.get(reason, 0) + 1
        rtxt = (", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))
                or "-")
        lines.append(
            f"  replica{rid}: {len(admits)} request(s) "
            f"({len(replayed)} replayed-in), {len(closes)} closed, "
            f"{len(admits) - len(closes)} left in flight here, "
            f"{tokens} token(s); closes: {rtxt}")
        hits = float(prefix.get("Serve/prefix.hits", 0) or 0)  # dslint: allow(undeclared-event-name) read-side filter
        misses = float(prefix.get("Serve/prefix.misses", 0) or 0)  # dslint: allow(undeclared-event-name) read-side filter
        if hits + misses > 0:
            saved = prefix.get("Serve/prefix.tokens_saved", 0)  # dslint: allow(undeclared-event-name) read-side filter
            lines.append(
                f"    prefix reuse: hit ratio "
                f"{hits / (hits + misses):.3f} ({int(hits)} hit(s) / "
                f"{int(hits + misses)} lookup(s)), "
                f"{int(saved or 0)} token(s) of prefill skipped")
        if recover_hist:
            qs = {q: _pod.histogram_quantile(
                tuple(recover_hist["buckets"]), recover_hist["counts"],
                recover_hist["count"], q) for q in (0.5, 0.95, 0.99)}
            qtxt = ", ".join(f"p{int(q * 100)}={v:.3f}s"
                             for q, v in qs.items() if v is not None)
            lines.append(f"    time_to_recover "
                         f"({recover_hist['count']} sample(s)): {qtxt}")
        try:
            with open(os.path.join(jdir, "failover_claim.json")) as f:
                claims += len((json.load(f) or {}).get("uids", {}))
        except (OSError, ValueError):
            pass
    dupes = {u: n for u, n in close_counts.items() if n > 1}
    lines.append(f"  fleet: {len(all_uids)} unique request(s), "
                 f"{len(closed_uids)} closed, "
                 f"{len(all_uids - closed_uids)} in flight, "
                 f"{total_tokens} token(s)")
    lines.append(f"  close records per closed request: "
                 + ("exactly one (exactly-once holds)" if not dupes else
                    f"DUPLICATES for {len(dupes)} uid(s): "
                    f"{sorted(dupes)[:10]}"))
    # router stream: route times (for TTFT join), failover ledger, the
    # final Fleet/* counter snapshot from the dump marker
    route_t: Dict[Any, float] = {}
    deaths = replays = replay_sheds = sheds = 0
    counters: Dict[str, Any] = {}
    for path in router_files:
        for rec in load_records(path):
            name = rec.get("name")
            data = rec.get("data") or {}
            if name == "fleet/route" and "uid" in data:
                t = rec.get("t")
                if t is not None:
                    route_t.setdefault(data["uid"], float(t))
            elif name == "fleet/death":
                deaths += 1
            elif name == "fleet/shed":
                sheds += 1
            elif name == "fleet/failover":
                if data.get("outcome") == "shed":
                    replay_sheds += 1
                elif data.get("outcome") in ("replayed", "dispatched"):
                    replays += 1
            if rec.get("kind") == "dump":
                for k, v in ((rec.get("data") or {}).get("metrics", {})
                             .get("counters", {})).items():
                    if k.startswith("Fleet/"):
                        counters[k] = v
    if router_files:
        lines.append(f"  failover: {deaths} death(s), {claims} claimed "
                     f"stream(s), {replays} replay(s), "
                     f"{replay_sheds} replay shed(s), "
                     f"{sheds} router shed record(s)")
        ttfts = [first_emit_t[u] - t for u, t in route_t.items()
                 if u in first_emit_t and first_emit_t[u] >= t]
        if ttfts:
            qs = _simple_quantiles(ttfts)
            lines.append("  routed TTFT (" + f"{len(ttfts)} sample(s)): "
                         + ", ".join(f"p{int(q * 100)}={v:.3f}s"
                                     for q, v in qs.items()))
        for name in sorted(counters):
            lines.append(f"  {name} = {counters[name]}")
    elif claims:
        lines.append(f"  failover: {claims} claimed stream(s) "
                     f"(no router stream found)")
    return "\n".join(lines)


def requests_report(root: str, worst_n: int = 5, window_s: float = 60.0,
                    budget: float = 0.05) -> Optional[str]:
    """Request-time attribution: fuse the router stream + per-replica
    request journals under ``root`` into per-request span trees
    (``monitor/reqtrace.py``) and render where TTFT and ITL go — per-stage
    quantiles, tail attribution, reconciliation, SLO burn and worst-request
    waterfalls. ``root`` may be a fleet root (``replica*/`` + router
    stream) or a single journal directory."""
    rt = _load_monitor_module("reqtrace")
    streams, router_records = rt.load_root(root)
    traces = rt.join_traces(streams, router_records)
    if not traces:
        return None
    att = rt.attribution(traces, worst_n=worst_n, slo_window_s=window_s,
                         slo_budget=budget)

    def _qline(qs: Dict[str, Any]) -> str:
        return ", ".join(f"{q}={_fmt_s(v)}" for q, v in qs.items()
                         if q.startswith("p") and v is not None)

    lines = [f"request-time attribution — {att['requests']} request(s), "
             f"{att['closed']} closed, {att['edge_sheds']} edge shed(s), "
             f"{att['failover_spans']} failover span(s)"]
    if att["multi_close"]:
        lines.append(f"  WARNING: {att['multi_close']} request(s) closed "
                     f"more than once — exactly-once is broken")
    rec = att["reconciliation"]
    if rec["median_frac"] is not None:
        flag = "" if (rec["within_5pct_frac"] or 0) >= 0.95 else \
            "  <-- BELOW CONTRACT (stage sums should cover >=95% of wall)"
        lines.append(
            f"  reconciliation: median {100 * rec['median_frac']:.1f}% of "
            f"wall attributed, min {100 * rec['min_frac']:.1f}%, "
            f"{100 * rec['within_5pct_frac']:.1f}% of requests within "
            f"5%{flag}")
    if att["ttft"].get("p50") is not None:
        lines.append(f"  TTFT: {_qline(att['ttft'])}")
    if att["ttft_by_stage"]:
        lines.append("  TTFT by stage (admit -> first token):")
        ranked = sorted(att["ttft_by_stage"].items(),
                        key=lambda kv: -kv[1]["mean_s"])
        for stage, qs in ranked:
            if qs["mean_s"] <= 0 and not any(
                    qs.get(p) for p in ("p50", "p95", "p99")):
                continue
            dom = "  <-- dominant" if stage == att["dominant_ttft_stage"] \
                else ""
            lines.append(f"    {stage:<14}mean={_fmt_s(qs['mean_s'])}  "
                         f"{_qline(qs)}{dom}")
    if att["itl_by_stage"]:
        itl = {s: qs for s, qs in att["itl_by_stage"].items()
               if qs["mean_s"] > 0}
        if itl:
            lines.append("  ITL by stage (per token past the first):")
            for stage, qs in sorted(itl.items(),
                                    key=lambda kv: -kv[1]["mean_s"]):
                lines.append(f"    {stage:<14}mean={_fmt_s(qs['mean_s'])}  "
                             f"{_qline(qs)}")
    tail = att["tail"]
    if tail:
        lines.append(f"  tail attribution (slowest {tail['tail_n']} vs "
                     f"median {tail['median_n']}):")
        for stage, d in sorted(tail["by_stage"].items(),
                               key=lambda kv: -kv[1]["growth_s"]):
            if abs(d["growth_s"]) < 1e-9 and d["tail_s"] <= 0:
                continue
            dom = "  <-- tail driver" if stage == tail["dominant_stage"] \
                else ""
            lines.append(f"    {stage:<14}median={_fmt_s(d['median_s'])}  "
                         f"tail={_fmt_s(d['tail_s'])}  "
                         f"growth={_fmt_s(d['growth_s'])}{dom}")
    if att["decode_rounds"]:
        lines.append(f"  decode rounds: {att['decode_rounds']}")
    rp = rt.round_phases(streams)
    if rp:
        launch = (f"; forward launched {_qline(rp['launch_s'])} into it"
                  if rp["launch_s"] else "")
        lines.append(f"  round phases ({rp['rounds']} round record(s); round "
                     f"{_qline(rp['round_s'])}{launch}):")
        for phase, qs in sorted(rp["phases"].items(),
                                key=lambda kv: -kv[1]["mean_s"]):
            if qs["mean_s"] > 0:
                lines.append(f"    {phase:<14}mean={_fmt_s(qs['mean_s'])}  "
                             f"{_qline(qs)}")
        loop = rp.get("loop")
        if loop:
            lines.append(
                f"  looped stack: {loop['passes']} passes a forward over "
                f"shared weights, {loop['kv_rows']} cache rows a token"
                + (f"; rows by exit pass {loop['exit_pass']}"
                   if loop["exit_pass"] is not None else ""))
        # what each program's forward covered, a round's mean (rows: the
        # static rows it ran at, pads included; ahead: the share of its
        # launches made before the last forward's tokens were read back;
        # spec-rows: rows launched for a stream an EOS had ended)
        # a program that holds a share of the experts counts their rows too
        share = any("moe_rows" in m for m in rp["programs"].values())
        # a forward whose grouped GEMMs counted their row tiles: how full
        # they were, and the tiles an expert's one read of weights served
        tiled = any(m.get("moe_tiles") for m in rp["programs"].values())
        # a sparse-attention indexer: the pairs and one-row tokens SELECTED
        dsa = any("sel_pairs" in m for m in rp["programs"].values())

        def tile_cols(m):
            tiles = m.get("moe_tiles", 0)
            if not tiles:
                return f"{'-':>11}{'-':>11}{'-':>14}"
            rows = m.get("moe_rows",
                         m["tokens"] * m.get("moe_rows_a_token", 0))
            room = tiles * m.get("moe_tile_rows", 0)
            return (f"{m.get('moe_tile_rows', 0):>11.0f}"
                    f"{100 * rows / room if room else 0:>10.1f}%"
                    f"{tiles / max(m.get('moe_touched', 0), 1e-9):>14.2f}")

        def warm_col(m):
            """Of the live attention tiles, those whose first KV step the
            tile before them fetched."""
            live = m.get("decode_rows", 0) + m.get("atoms", 0)
            return f"{100 * m.get('warm_tiles', 0) / live:.1f}%" if live \
                else "-"
        lines.append(f"    {'launched':<20}{'rounds':>7}{'seqs':>8}"
                     f"{'tokens':>9}{'prompt':>9}{'context':>10}"
                     f"{'kv blocks':>11}{'1-row':>8}{'atoms':>8}"
                     f"{'pairs':>12}{'1-row-ctx':>11}{'experts':>9}"
                     f"{'ahead':>7}{'spec-rows':>11}"
                     f"{'step-keys':>11}{'tile-keys':>11}{'rows':>8}"
                     f"{'warm':>8}"
                     + (f"{'exp-rows':>10}" if share else "")
                     + (f"{'tile-rows':>11}{'tile-fill':>11}{'tiles/expert':>14}"
                        if tiled else "")
                     + (f"{'sel-pairs':>12}{'1-row-sel':>11}{'1-row-walk':>12}"
                        if dsa else ""))
        for name, m in sorted(rp["programs"].items()):
            lines.append(f"    {name:<20}{m['rounds']:>7}{m['n_seqs']:>8.1f}"
                         f"{m['tokens']:>9.1f}{m['prefill_tokens']:>9.1f}"
                         f"{m['ctx_tokens']:>10.1f}{m['kv_blocks']:>11.1f}"
                         f"{m.get('decode_rows', 0):>8.1f}"
                         f"{m.get('atoms', 0):>8.1f}"
                         f"{m.get('attn_pairs', 0):>12.1f}"
                         f"{m.get('dec_ctx_tokens', 0):>11.1f}"
                         f"{m.get('moe_touched', 0):>9.1f}"
                         f"{m.get('ahead', 0):>7.2f}"
                         f"{m.get('spec_rows', 0):>11.2f}"
                         f"{m.get('kv_step_keys', 0):>11.1f}"
                         f"{m.get('kv_tile_keys', 0):>11.1f}"
                         f"{m.get('rows', 0):>8.1f}"
                         f"{warm_col(m):>8}"
                         + (f"{m.get('moe_rows', 0):>10.1f}" if share
                            else "") + (tile_cols(m) if tiled else "")
                         + (f"{m.get('sel_pairs', 0):>12.1f}"
                            f"{m.get('dec_sel_tokens', 0):>11.1f}"
                            f"{m.get('dec_walk_keys', 0):>12.1f}"
                            if dsa else ""))
    if att["cached_prefix_tokens_mean"]:
        lines.append(f"  cached prefix: "
                     f"{att['cached_prefix_tokens_mean']:.1f} token(s)/request "
                     f"mean")
    burn = att["slo_burn"]
    if burn["windows"]:
        worst = max(burn["windows"], key=lambda w: w["burn"])
        lines.append(
            f"  SLO burn ({burn['window_s']:.0f}s windows, budget "
            f"{burn['budget']:.0%}): max burn {burn['max_burn']:.2f}x "
            f"(worst window: {worst['n']} request(s), "
            f"{100 * worst['miss_frac']:.1f}% missed)"
            + ("  <-- BUDGET EXHAUSTING" if burn["max_burn"] > 1 else ""))
    if att["worst"]:
        lines.append(f"  worst {len(att['worst'])} request(s) by TTFT:")
        for w in att["worst"]:
            path = "->".join(w["replica_path"]) or "?"
            stages = ", ".join(f"{s}={_fmt_s(v)}"
                               for s, v in sorted(
                                   w["stages"].items(),
                                   key=lambda kv: -kv[1]) if v > 0)
            lines.append(
                f"    uid {w['uid']}: ttft={_fmt_s(w['ttft_s'])} "
                f"wall={_fmt_s(w['wall_s']) if w['wall_s'] else '?'} "
                f"tokens={w['tokens']} replicas={path}"
                + (f" replays={w['replays']}" if w["replays"] else "")
                + f" [{w['close_reason'] or 'open'}]")
            if stages:
                lines.append(f"      {stages}")
            if w["unattributed_s"] > 1e-9:
                lines.append(f"      unattributed="
                             f"{_fmt_s(w['unattributed_s'])}")
    return "\n".join(lines)


def straggler_summary(per_rank: Dict[int, List[Dict[str, Any]]]) -> List[str]:
    """``per_rank`` is keyed by rank id (inferred by :func:`render` from
    filenames / stream metadata — callers no longer hand-build the dict)."""
    lines = ["stragglers (per-host accumulated step wall-clock)"]
    totals = {}
    for rank, records in per_rank.items():
        tot = sum(r.get("dur", 0.0) for r in records
                  if r.get("kind") == "span" and r.get("name") == "step")
        totals[f"rank{rank}"] = tot
    if not totals:
        lines.append("  (no step spans)")
        return lines
    lo = min(totals.values())
    for name in sorted(totals):
        tot = totals[name]
        flag = "  <-- straggler" if lo > 0 and tot > 1.2 * lo else ""
        lines.append(f"  {name:<10}{_fmt_s(tot):>12}{flag}")
    return lines


def render(paths: List[str], last: int = 20) -> Optional[str]:
    paths = _pod.discover_rank_files(paths)
    per_path = {p: load_records(p) for p in paths}
    per_path = {p: r for p, r in per_path.items() if r}
    if not per_path:
        return None
    # key by inferred rank id (filename rank<N> convention, else the
    # stream's own meta record, else position) — the straggler table wants
    # ranks, not paths
    per_rank: Dict[int, List[Dict[str, Any]]] = {}
    for i, (p, records) in enumerate(per_path.items()):
        rank = _pod.infer_rank(p, records)
        if rank is None or rank in per_rank:
            rank = next(n for n in range(len(per_path) + len(per_rank))
                        if n not in per_rank)
        per_rank[rank] = records
    first_rank = min(per_rank)
    first = per_rank[first_rank]
    out: List[str] = []
    n_total = sum(len(r) for r in per_rank.values())
    out.append(f"flight recorder report — {len(per_rank)} file(s), "
               f"{n_total} records")
    times = [r["t"] for r in first if "t" in r]
    if times:
        out.append(f"wall span: {max(times) - min(times):.2f}s "
                   f"({len(first)} records in rank{first_rank})")
    out.append("")
    out.extend(step_timeline(first, last))
    out.append("")
    out.extend(goodput_summary(first))
    out.append("")
    out.extend(events_summary(first))
    all_records = [r for recs in per_rank.values() for r in recs]
    offload = offload_summary(all_records)
    if offload:
        out.append("")
        out.extend(offload)
    health = health_summary(all_records)
    if health:
        out.append("")
        out.extend(health)
    recovery = serve_recovery_summary(all_records)
    if recovery:
        out.append("")
        out.extend(recovery)
    prefix = serve_prefix_summary(all_records)
    if prefix:
        out.append("")
        out.extend(prefix)
    if len(per_rank) > 1:
        out.append("")
        out.extend(straggler_summary(per_rank))
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Render a flight-recorder JSONL into a step-timeline / "
                    "goodput / straggler summary.")
    ap.add_argument("files", nargs="+",
                    help="flight-recorder JSONL file(s), glob pattern(s) or "
                         "directories — one stream per rank")
    ap.add_argument("--last", type=int, default=20,
                    help="how many trailing steps to show in the timeline")
    ap.add_argument("--pod", action="store_true",
                    help="pod-scope report instead (alias for "
                         "tools/pod_report.py: clock-aligned skew, straggler "
                         "ledger, per-class bandwidth decomposition)")
    ap.add_argument("--fleet", action="store_true",
                    help="serving-fleet report: merged cross-replica journal "
                         "lifecycle, failover ledger and routed-TTFT "
                         "quantiles from a fleet root directory "
                         "(replica*/ + router.jsonl)")
    ap.add_argument("--requests", action="store_true",
                    help="request-time attribution: journal-joined "
                         "per-request span trees — per-stage TTFT/ITL "
                         "decomposition, tail attribution, SLO burn and "
                         "worst-request waterfalls from a fleet root or "
                         "journal directory")
    ap.add_argument("--setup", action="store_true",
                    help="the set-up ledger: one line a program (trace, "
                         "lower, compile or load, executables, how many the "
                         "persistent cache missed) and the set-up span tree "
                         "with self times, from a flight-recorder JSONL or "
                         "a JSON dump of telemetry.setup_ledger()")
    ap.add_argument("--worst", type=int, default=5,
                    help="worst-request exemplars to show with --requests")
    ap.add_argument("--slo-window", type=float, default=60.0,
                    help="SLO burn sliding window seconds (--requests)")
    ap.add_argument("--slo-budget", type=float, default=0.05,
                    help="SLO error budget fraction (--requests)")
    args = ap.parse_args(argv)
    if args.pod:
        return pod_report.main([*args.files, "--last", str(args.last)])
    if args.setup:
        reports = [setup_report(os.path.expanduser(p)) for p in args.files]
        reports = [r for r in reports if r]
        if not reports:
            print("no set-up ledger found in any input file",
                  file=sys.stderr)
            return 2
        print("\n\n".join(reports))
        return 0
    if args.requests:
        reports = [requests_report(os.path.expanduser(p),
                                   worst_n=args.worst,
                                   window_s=args.slo_window,
                                   budget=args.slo_budget)
                   for p in args.files]
        reports = [r for r in reports if r]
        if not reports:
            print("no request traces found in any input directory",
                  file=sys.stderr)
            return 2
        print("\n\n".join(reports))
        return 0
    if args.fleet:
        reports = [fleet_summary(os.path.expanduser(p)) for p in args.files]
        reports = [r for r in reports if r]
        if not reports:
            print("no fleet records found in any input directory",
                  file=sys.stderr)
            return 2
        print("\n\n".join(reports))
        return 0
    report = render([os.path.expanduser(p) for p in args.files],
                    last=args.last)
    if report is None:
        print("no records found in any input file", file=sys.stderr)
        return 2
    print(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
