"""The sparse-expert MLP's share of the device's busy time in the traced
window: the operations under the program's ``moe_route`` (router, softmax,
top-k, the sort by expert and the gather of rows), ``moe_experts`` (the
three grouped GEMMs and the activation between them) and ``moe_combine``
(weights, scatter-add back to tokens) scopes, found by instruction name
(``benchmark/scopes.py``). The GEMMs themselves are the TPU compiler's own
``ragged-dot-*`` custom calls, which carry no scope: found by that name."""
from benchmark import scopes, trace

SCOPES = ("moe_route", "moe_experts", "moe_combine")


def read(obs):
    ops = scopes.scoped_ops(obs, SCOPES, scopes.RAGGED_DOT_KERNELS)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
