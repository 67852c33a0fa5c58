"""The power-retention layers' share of the device's busy time in the traced
window: the operations under the program's ``ret_proj`` (the q, k, v and
output projections with their norms and rotary), ``ret_gate`` (the gate's
projection) and ``ret_scan`` (the recurrence: the in-place decode step, the
chunked form's pieces) scopes, found by instruction name
(``benchmark/scopes.py``).

Nothing to read, and ``None``: a program without the scopes (every model
but one with power-retention layers; every commit before the one that added
them)."""
from benchmark import scopes, trace

SCOPES = ("ret_proj", "ret_gate", "ret_scan")


def read(obs):
    ops = scopes.scoped_ops(obs, SCOPES)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
