#!/usr/bin/env python
"""Pod-scope flight-recorder report: cross-rank fusion, skew, bandwidth.

Fuses N rank-local JSONL streams (``monitor/telemetry.py`` flight recorder)
into one cluster timeline via ``monitor/pod.py``: per-step arrival skew
with last-arriving-rank attribution (the straggler ledger), and the
comm/compute decomposition joining measured step spans against the static
collective census — bytes moved, time attributed, effective bandwidth per
traffic class, and a per-step ``comm_bound_frac``. Offline and
device-free (no backend/session initialization): safe on a login node
over files rsynced from a dead job.

Usage::

    python tools/pod_report.py telemetry_logs/
    python tools/pod_report.py 'logs/flightrec_rank*.jsonl' --last 30
    python tools/pod_report.py logs/ --compute-s 0.012 --link-gbps 100 \
        --json pod_report.json

Inputs may be directories (their ``flightrec*.jsonl``), glob patterns, or
explicit files; rank ids come from the ``rank<N>`` filename convention or
the stream's own meta record. Torn/truncated streams (a rank killed
mid-write) are salvaged and flagged, never fatal.

The per-class table needs a static census in the streams — run with
``engine.emit_comm_census()`` (the multichip dryrun does) — or
pass ``--census census.json`` (a ``CollectiveClasses.summary()`` dict).

Exit code 0 on success, 2 when no input yields any records.
"""
import argparse
import importlib.util
import json
import os
import sys
from typing import List, Optional

# load monitor/pod.py by file path, NOT through the package: the package
# __init__ imports jax, and this tool must run on a login node without it
# (pod.py is deliberately stdlib-only)
_POD_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeedsyclsupport_tpu", "monitor",
    "pod.py")
_spec = importlib.util.spec_from_file_location("_dstpu_pod", _POD_PATH)
pod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(pod)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Fuse per-rank flight-recorder JSONLs into a pod "
                    "timeline / straggler / bandwidth report.")
    ap.add_argument("inputs", nargs="+",
                    help="directories, globs or files of per-rank JSONLs")
    ap.add_argument("--last", type=int, default=20,
                    help="trailing steps to show in the timeline")
    ap.add_argument("--census", metavar="JSON",
                    help="static census classes file (overrides any "
                         "comm/census record in the streams)")
    ap.add_argument("--compute-s", type=float, default=None,
                    help="comm-free compute time per step (e.g. a "
                         "single-chip calibration); default: the minimum "
                         "observed per-rank step duration")
    ap.add_argument("--link-gbps", type=float, default=None,
                    help="interconnect capacity hint enabling the "
                         "exposed-vs-overlapped comm split")
    ap.add_argument("--json", metavar="OUT",
                    help="also write the serialized report (schema "
                         "monitor/pod.py POD_REPORT_KEYS) to this file")
    args = ap.parse_args(argv)

    census = None
    if args.census:
        try:
            with open(args.census) as f:
                census = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read census {args.census}: {e}",
                  file=sys.stderr)
            return 2

    report = pod.pod_report_from_paths(
        args.inputs, census=census, compute_s=args.compute_s,
        link_gbps=args.link_gbps)
    if report is None:
        print("no flight-recorder records found in any input",
              file=sys.stderr)
        return 2
    for rank in report.truncated_ranks:
        stream_path = report.source_files.get(rank, "?")
        print(f"note: rank{rank} stream is truncated (salvaged partial "
              f"records from {stream_path})", file=sys.stderr)
    if report.comm_hang is not None:
        h = report.comm_hang
        who = (f"rank{h['culprit_rank']} ({h.get('culprit_reason')})"
               if h.get("culprit_rank") is not None else "unattributed")
        print(f"COMM HANG: step {h['step']} — culprit {who}; see the "
              f"'collective hang' section below", file=sys.stderr)
    print(report.render(last=args.last))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report.to_dict(), f, indent=2)
        print(f"\nserialized report -> {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
