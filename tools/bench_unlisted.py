#!/usr/bin/env python
"""One traced benchmark run that ALSO reads the per-layer entries the cell
does not list, and breaks the traced rounds' device time down by program,
scope and operation: where PERF.md section 5 takes the numbers of a cell
that has joined no accepted entry yet (``solar2-agent-sat``, PR 55).

Usage, from the root of a checkout, on the chip::

    python tools/bench_unlisted.py [--readers a,b] [--scopes x,y] \\
        --workload solar2-agent-sat --seed 7 --seconds 51 --trace 1

Everything after the two options is ``benchmark.run``'s. The result line is
the harness's own with the extra entries in it (an entry whose reader finds
nothing is left out, as always); the breakdown goes to stderr on lines that
start ``BREAKDOWN`` (by scope, by operation, the six largest copies
with their shapes and layouts, and the serving forwards' time by MFU region
and by what each region's operations end in: ``fwd_split_pct.split``).
``benchmark/`` is not edited: the extra entries are
handed to ``spec.Bench`` for the length of this process. An entry that
shares its reader with another takes the suffix its own cells have
(``decode_fwd_ms.moe``); the defaults are those of a sparse serving cell
with recurrent state."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run, scopes, spec, trace  # noqa: E402
from benchmark.metrics import fwd_split_pct  # noqa: E402

READERS = ("decode_fwd_ms.moe", "ragged_fwd_ms.moe", "moe_share_pct",
           "moe_roofline", "expert_load_max_over_mean", "live_seqs_mean",
           "kv_bytes_per_token.tok", "serve_idle_pct.moe", "round_max_ms",
           "round_p50_ms.moe", "launch_ahead_pct", "ragged_row_fill_pct",
           "ragged_tile_fill_pct", "moe_tile_fill_pct",
           "serve_program_gib.moe", "share_ragged_rounds_pct.moe",
           "state_bytes_per_seq", "kv_step_fill_pct", "itl_p99_ms.moe")
SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_scan", "kda_step",
          "kda_chunk", "attn_gate", "moe_route", "moe_experts", "moe_combine",
          "moe_shared", "kv_pool_write")
# kernels and grouped products carry no scope of their own in the trace
KERNELS = (("kda_state_step", "kda_step"), ("ragged-dot", "moe_experts"),
           ("grouped_", "moe_experts"))


def breakdown(labels):
    """A reader that prints the traced rounds' device time and reads
    nothing."""
    def read(obs):
        ops = scopes.scoped_ops(obs, labels, KERNELS)
        tr = obs["trace"]
        plane = sorted(tr["devices"])[0]
        lo, hi = obs["trace_window"]
        label_at = {(p, s): lab for lab, p, s, _d in ops}
        by, top, copies, execs = {}, {}, {}, {}
        names = trace.program_names(tr, plane)
        for m in tr["devices"][plane]["modules"]:
            if lo <= m[1] <= hi:
                execs[names[m[0]]] = execs.get(names[m[0]], 0) + 1
        for p, text, start, dur in trace.ops_by_program(tr, plane):
            if not lo <= start <= hi:
                continue
            lab = label_at.get((p, start), "-")
            by[(p, lab)] = by.get((p, lab), 0.0) + dur
            key = (p, lab, trace.op_kind(text), trace.op_name(text))
            top[key] = top.get(key, 0.0) + dur
            if key[2] == "copy":
                was = copies.get((p, text), (0.0, 0))
                copies[(p, text)] = (was[0] + dur, was[1] + 1)
        print("BREAKDOWN execs", json.dumps(execs), file=sys.stderr)
        for (p, lab), d in sorted(by.items(), key=lambda kv: -kv[1]):
            print(f"BREAKDOWN scope {p:16s} {lab:14s} {1e3 * d:9.2f} ms  "
                  f"{1e3 * d / max(1, execs.get(p, 1)):7.3f} ms/exec",
                  file=sys.stderr)
        for key, d in sorted(top.items(), key=lambda kv: -kv[1])[:70]:
            n = max(1, execs.get(key[0], 1))
            print(f"BREAKDOWN op {key[0]:16s} {key[1]:12s} {key[2]:7s} "
                  f"{key[3]:40s} {1e3 * d:9.2f} ms {1e3 * d / n:7.3f} ms/exec",
                  file=sys.stderr)
        # a copy's name says nothing: the largest with their shapes and
        # layouts, as the trace spells them
        for (p, text), (d, n) in sorted(copies.items(),
                                        key=lambda kv: -kv[1][0])[:6]:
            print(f"BREAKDOWN copy {p:16s} {1e3 * d:9.2f} ms "
                  f"{n / max(1, execs.get(p, 1)):5.2f} a forward  "
                  f"{text[:300]}", file=sys.stderr)
        regions(obs)
        return None
    return read


def regions(obs):
    """The window's busy time by owner (an MFU region inside the two
    forwards, ``unmapped``, any other program by its name) and, inside each
    region, by what its operations end in."""
    table = fwd_split_pct.split(obs)
    if not table:
        print("BREAKDOWN region -  (the program publishes no map or opens "
              "no region)", file=sys.stderr)
        return
    busy = table["busy_s"] or 1.0
    print(f"BREAKDOWN region {'busy':16s} {1e3 * busy:9.2f} ms  of which "
          f"the forwards {1e3 * table['forwards_s']:9.2f} ms",
          file=sys.stderr)
    for owner, s in sorted(table["owners"].items(), key=lambda kv: -kv[1]):
        print(f"BREAKDOWN region {owner:16s} {1e3 * s:9.2f} ms "
              f"{100 * s / busy:6.2f} %", file=sys.stderr)
    for owner, row in table["roots"].items():
        for root, s in sorted(row.items(), key=lambda kv: -kv[1])[:12]:
            print(f"BREAKDOWN root {owner:10s} {root:28s} {1e3 * s:9.2f} ms "
                  f"{100 * s / busy:6.2f} %", file=sys.stderr)


def unlisted(extra, labels):
    """``spec.Bench``'s ``metrics_of`` and ``reader`` with the entries named
    in ``extra`` added to every cell's per-layer list, and the breakdown
    over ``labels`` behind them."""
    metrics_of, reader = spec.Bench.metrics_of, spec.Bench.reader

    def with_unlisted(self, cell, section):
        out = metrics_of(self, cell, section)
        if section != "per_layer":
            return out
        have = {m["name"] for m in out}
        listed = [m for m in self.doc["per_layer"] + self.doc["end_to_end"]
                  if m["name"] in extra - have]
        # a reader with a file and no entry (the list is full) reads too
        bare = sorted(extra - have - {m["name"] for m in listed})
        return out + listed + [{"name": n, "unit": "x"} for n in bare] \
            + [{"name": "_breakdown", "unit": "x"}]

    def read_unlisted(self, name):
        return breakdown(labels) if name == "_breakdown" \
            else reader(self, name)

    return with_unlisted, read_unlisted


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--readers", default=",".join(READERS))
    ap.add_argument("--scopes", default=",".join(SCOPES))
    args, rest = ap.parse_known_args(argv)
    spec.Bench.metrics_of, spec.Bench.reader = unlisted(
        set(filter(None, args.readers.split(","))),
        tuple(filter(None, args.scopes.split(","))))
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
