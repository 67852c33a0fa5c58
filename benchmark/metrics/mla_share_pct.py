"""Latent attention's share of the device's busy time in the traced window:
the operations under the program's ``mla_proj`` scope (the down and up
projections of q and kv with their two norms, rotary, the pool write, the
output projection) and ``mla_absorb`` scope (``q_nope W_UK`` into the query,
``W_UV`` out of the attended latent), found by instruction name
(``benchmark/scopes.py``), and both paged kernels reading the latent pool,
Pallas custom calls found by the names they carry (``paged_decode``,
``ragged_prefill``). A program without the scopes (no latent attention, or
every commit before the one that added them) gives nothing to read."""
from benchmark import scopes, trace

SCOPES = ("mla_proj", "mla_absorb")
KERNELS = (("paged_decode", "mla_decode"), ("ragged_prefill", "mla_prefill"))


def read(obs):
    ops = scopes.scoped_ops(
        obs, SCOPES + tuple(label for _p, label in KERNELS), KERNELS)
    if not ops or not any(label == "mla_proj" for label, *_ in ops):
        return None     # the kernels alone are not latent attention
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
