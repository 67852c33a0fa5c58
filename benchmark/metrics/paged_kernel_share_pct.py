"""The Pallas kernels' (paged decode and ragged prefill custom calls) share
of the device's busy time in the traced window. Not a roofline share: that
needs each call's context lengths, which the program does not count yet."""
from benchmark import trace


def read(obs):
    tr = obs["trace"]
    if tr is None:
        return None
    lo, hi = obs["trace_window"]
    plane = sorted(tr["devices"])[0]
    ops = trace.leaf_ops(tr, plane)
    kernels = [e for e in ops if trace.op_kind(e[0]) == "kernel"]
    busy = trace.union_s(ops, lo, hi)
    return 100.0 * trace.union_s(kernels, lo, hi) / busy if busy else None
