"""The atoms' kernel of ONE attention kind against the device's roofline,
over the traced ``ragged_forward`` rounds of a stack of two attention kinds
(``ModelConfig.attn_period``). ``kind``: ``swa`` (the windowed layers) or
``full``.

What the kernel cannot avoid, in each layer of the kind and each forward,
from the program's ``round`` record (``ragged.window_work``, counted on the
host from the chunks alone): for every (row, cached token) pair the row may
see and every query head, the score and the value product, ``2 x 2 x
head_dim`` FLOPs; and the K and V rows every atom must read, all KV heads.
``full``: the pairs of the causal triangle (``attn_pairs``) and, an atom,
every key up to its last row (``full_atom_keys``). ``swa``: ONLY the pairs
and keys inside the window (``swa_pairs``, ``swa_atom_keys``): a kernel that
walked a windowed layer's whole context would take longer for the same
count and read LOW, not high. Through ``flops.roofline_seconds``, over the
device time of the ``paged_<kind>_prefill`` custom calls inside each
forward's execution. A floor (tokens, not the whole blocks and steps the
kernel moves; none of a diagonal block's masked half), so it cannot pass
100.

Nothing to read, and ``None``: records without the counts (every model of
one attention kind), a trace without such a round or such a kernel."""
from benchmark import flops, spans
from benchmark.metrics.attn_kind_share_pct import kind_kernels

FIELDS = {"swa": ("swa_pairs", "swa_atom_keys"),
          "full": ("attn_pairs", "full_atom_keys")}


def prefill_work(arch, kind, pairs, atom_keys, itemsize=2):
    """``(FLOPs, bytes)`` of one forward's ``paged_<kind>_prefill`` calls,
    all layers of the kind: ``pairs`` (row, key) pairs and ``atom_keys``
    keys read in each."""
    layers = sum((k == "sliding") == (kind == "swa")
                 for k in arch["layer_kinds"])
    d = arch["head_dim"]
    return (layers * pairs * arch["num_heads"] * 4 * d,
            layers * atom_keys * 2 * arch["num_kv_heads"] * d * itemsize)


def read(obs, kind):
    rounds = spans.traced_rounds(obs)
    if not rounds:
        return None
    arch = obs["family"].arch(obs["config"])
    kernels = kind_kernels(obs["trace"], kind, "prefill")
    if "layer_kinds" not in arch or not kernels:
        return None
    pairs_of, keys_of = FIELDS[kind]
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        ran = d["program"] == "ragged_forward" and d.get(keys_of) \
            and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran:
            continue
        seconds = sum(dur for _p, _t, start, dur in kernels
                      if ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(*prefill_work(
            arch, kind, d[pairs_of], d[keys_of]), obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
