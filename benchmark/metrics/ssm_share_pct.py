"""The Mamba-2 mixers' share of the device's busy time in the traced window:
the operations under the program's ``ssm_proj`` (in and out projections),
``ssm_conv`` (the depthwise convolution and its tail in the state pool),
``ssm_scan`` (the recurrence with its ``D`` skip: the in-place decode step,
the chunked scan's pieces) and ``ssm_gate`` (the gate and its grouped norm)
scopes, found by instruction name (``benchmark/scopes.py``).

Nothing to read, and ``None``: a program without the scopes (every model
but one with Mamba layers; every commit before the one that added them)."""
from benchmark import scopes, trace

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")


def read(obs):
    ops = scopes.scoped_ops(obs, SCOPES)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
