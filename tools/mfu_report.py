#!/usr/bin/env python
"""Offline MFU-ledger report: where did the step time go?

Renders the artifacts ``Engine.mfu_ledger()`` persists next to a captured
clean-step profiler window (``telemetry.mfu``) — or any bare
``trace.json.gz`` + opmap pair — into the step-time attribution ledger:
achieved MFU, the gap waterfall (hardware peak → roofline-achievable →
measured), per-region measured-vs-achievable time with bound-by verdicts,
the region x pass table (forward, backward, remat's recomputed forward: the
same regions and passes the benchmark's ``train_*_ms`` entries read, from the
same ``mfu_opmap.json``), and the region↔step reconciliation. Offline and device-free (no jax, no
backend): safe on a login node over files rsynced from a dead job — the
``pod_report.py``/``trace_report.py`` contract.

Usage::

    python tools/mfu_report.py telemetry_logs/mfu_trace_rank0
    python tools/mfu_report.py run.trace.json.gz --opmap mfu_opmap.json \
        --roofline mfu_roofline.json --step-s 0.95
    python tools/mfu_report.py telemetry_logs/mfu_trace_rank0 --json out.json

The input directory is searched for the newest ``*.trace.json.gz`` plus the
sidecar ``mfu_opmap.json`` / ``mfu_roofline.json`` / ``mfu_window.json``
the engine wrote. A truncated trace (killed mid-write) is parse-salvaged
and flagged, never fatal. Without a roofline sidecar the report is
measured-only (regions + categories, no waterfall/verdicts).

Exit code 0 on success, 2 when no trace yields any op events.
"""
import argparse
import importlib.util
import json
import os
import sys
from typing import List, Optional

# load monitor/mfu.py by file path, NOT through the package: the package
# __init__ imports jax, and this tool must run on a login node without it
# (mfu.py is deliberately stdlib-only)
_MFU_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeedsyclsupport_tpu", "monitor",
    "mfu.py")
_spec = importlib.util.spec_from_file_location("_dstpu_mfu", _MFU_PATH)
mfu = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mfu)


def _load_json(path: Optional[str], what: str) -> Optional[dict]:
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"  note: cannot read {what} {path}: {e}", file=sys.stderr)
        return None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Render a captured MFU trace window into the step-time "
                    "attribution ledger.")
    ap.add_argument("input",
                    help="trace dir (engine's mfu_trace_rank<N>, searched "
                         "for the newest trace + sidecar JSONs) or a bare "
                         "trace.json[.gz]")
    ap.add_argument("--opmap", help="mfu_opmap.json override")
    ap.add_argument("--roofline", help="mfu_roofline.json override")
    ap.add_argument("--window", help="mfu_window.json override")
    ap.add_argument("--step-s", type=float, default=None,
                    help="measured clean-step seconds (overrides the "
                         "window sidecar)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps covered by the trace window (default from "
                         "the window sidecar, else 1)")
    ap.add_argument("--json", metavar="OUT",
                    help="also write the serialized ledger (schema "
                         "monitor/mfu.py MFU_LEDGER_KEYS) to this file")
    args = ap.parse_args(argv)

    root = os.path.expanduser(args.input)
    trace_path = mfu.find_trace(root)
    if trace_path is None:
        print(f"error: no trace file found under {root}", file=sys.stderr)
        return 2
    side = os.path.dirname(root) if os.path.isfile(root) else root
    opmap = _load_json(args.opmap or os.path.join(side, "mfu_opmap.json"),
                       "opmap")
    roofline = _load_json(
        args.roofline or os.path.join(side, "mfu_roofline.json"), "roofline")
    window = _load_json(
        args.window or os.path.join(side, "mfu_window.json"), "window") or {}

    events, meta = mfu.parse_trace(trace_path)
    if meta["truncated"]:
        print(f"  note: {trace_path}: truncated — salvaged "
              f"{meta['n_events']} event(s)", file=sys.stderr)
    if not events:
        print(f"error: {trace_path} holds no duration events",
              file=sys.stderr)
        return 2
    if opmap is None:
        print("error: no opmap (mfu_opmap.json) — the region join needs "
              "the compiled module's instruction->region map; pass "
              "--opmap or rerun with telemetry.mfu so the engine "
              "persists it", file=sys.stderr)
        return 2

    steps = args.steps or int(window.get("steps", 1))
    measured = mfu.measure_regions(events, opmap, steps=steps)
    if measured["n_mapped"] == 0:
        print("error: no trace event matches the opmap (trace and opmap "
              "from different programs?)", file=sys.stderr)
        return 2
    step_s = args.step_s or window.get("step_s")
    if step_s is None:
        # no measured step wall: the device-busy union is the best floor
        print("  note: no step wall (mfu_window.json / --step-s) — using "
              "the device-busy union; host time reads as 0",
              file=sys.stderr)
        step_s = measured["device_busy_s"]
    led = mfu.ledger(roofline, measured, float(step_s),
                     truncated_trace=meta["truncated"])
    print(mfu.render_ledger(led))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(led, f, indent=1, sort_keys=True)
        print(f"ledger written to {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
