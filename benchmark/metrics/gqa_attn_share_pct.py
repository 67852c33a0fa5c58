"""The softmax-attention layers' share of the device's busy time in the
traced window, for a stack that has them beside delta-rule layers (one in
four here): their paged-attention custom calls (the decode entry's one-row
tiles, ``paged_decode``, and the ragged kernel's atoms, ``ragged_prefill``,
by the names the program gives them) and the operations under the program's
``attn_gate`` scope (the output gate's projection, sigmoid and product).
What one full layer costs beside three state layers as contexts grow: the
state layers' cost is flat in the context, this one's is not.

Nothing to read, and ``None``: a program without the ``attn_gate`` scope
(every model but one with a gated attention layer; every commit before the
one that added it)."""
from benchmark import scopes, trace

# the paged kernels' custom calls, as ops/paged_attention.py names them
ATTENTION_KERNELS = ("paged_decode", "ragged_prefill")


def read(obs):
    gate = scopes.scoped_ops(obs, ("attn_gate",))
    if not gate:
        return None
    tr = obs["trace"]
    lo, hi = obs["trace_window"]
    plane = sorted(tr["devices"])[0]
    kernels = [(start, dur) for _p, text, start, dur
               in trace.ops_by_program(tr, plane)
               if trace.op_kind(text) == "kernel"
               and trace.op_name(text).startswith(ATTENTION_KERNELS)]
    busy = trace.union_s(trace.leaf_ops(tr, plane), lo, hi)
    ops = [(start, dur) for _l, _p, start, dur in gate] + kernels
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
