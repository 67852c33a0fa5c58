"""One attention kind's share of the device's busy time in the traced
window, for a stack of two attention kinds (windowed layers and full ones,
``ModelConfig.attn_period``): the kind's paged-attention custom calls, which
the program names ``paged_<kind>_prefill`` (the atoms of the chunks of two
tokens or more) and ``paged_<kind>_decode`` (the one-token chunks' one-row
tiles), found among the trace's kernels by that name. ``kind``: ``swa`` (the
windowed layers) or ``full``.

Nothing to read, and ``None``: a trace without such a kernel (every model of
one attention kind; every commit before the one that named them)."""
from benchmark import trace


def kind_kernels(tr, kind, entry=""):
    """The trace's leaf operations that are kernel ``paged_<kind>_<entry>``
    (``entry`` empty: both entries), as ``(program, text, start, dur)``."""
    plane = sorted(tr["devices"])[0]
    prefix = f"paged_{kind}_{entry}"
    return [e for e in trace.ops_by_program(tr, plane)
            if trace.op_kind(e[1]) == "kernel"
            and trace.op_name(e[1]).startswith(prefix)]


def read(obs, kind):
    tr = obs.get("trace")
    if tr is None:
        return None
    ops = kind_kernels(tr, kind)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
