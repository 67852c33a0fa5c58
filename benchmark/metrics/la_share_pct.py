"""The lightning layers' share of the device's busy time in the traced
window: the operations under the program's ``la_proj`` (the four projections
in and ``wo`` out), ``la_gate`` (the head norms, the rotation, the output
norm and gate) and ``la_scan`` (the recurrence: ``la_step``, the in-place
state step, and ``la_chunk``, the chunked form's pieces, inside it) scopes,
found by instruction name (``benchmark/scopes.py``); the state step's Pallas
call is found as a kernel by its name (Mamba-2's ``ssm_state_step``, whose
entry lightning attention runs through: ``ops/ssm.py``; a program with ``L``
layers has no Mamba-2 layer beside them).

Nothing to read, and ``None``: a program without the scopes (every model
but one with lightning layers; every commit before the one that added
them)."""
from benchmark.metrics import bsa_share_pct

SCOPES = ("la_proj", "la_gate", "la_scan", "la_step", "la_chunk")
KERNELS = (("ssm_state_step", "la_step"),)


def read(obs):
    if not hasattr(obs["family"], "la_step_flops"):
        return None
    return bsa_share_pct.read(obs, SCOPES, KERNELS)
