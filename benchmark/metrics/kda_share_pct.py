"""The delta-rule layers' share of the device's busy time in the traced
window: the operations under the program's ``kda_proj`` (the projections in
and out), ``kda_conv`` (the depthwise convolution and its tail), ``kda_gate``
(the decay, beta, the L2 norms, the gated output norm) and ``kda_scan`` (the
recurrence: ``kda_step``, the in-place state step, and ``kda_chunk``, the
chunked form's pieces, inside it) scopes, found by instruction name
(``benchmark/scopes.py``); the state step's Pallas call is found as a kernel
by its name (the TPU compiler gives a custom call no scope).

Nothing to read, and ``None``: a program without the scopes (every model
but one with delta-rule layers; every commit before the one that added
them)."""
from benchmark import scopes, trace

SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_scan", "kda_step",
          "kda_chunk")
# the state step's custom call: ``name="kda_state_step"`` (ops/kda.py)
KERNELS = (("kda_state_step", "kda_step"),)


def read(obs):
    ops = scopes.scoped_ops(obs, SCOPES, KERNELS)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
