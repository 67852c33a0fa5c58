"""Operations and bytes from shapes: the arithmetic behind every MFU and
roofline share the benchmark prints, and the table of peaks.

Kept with the benchmark so that no PR that claims a gain can change how its
gain is counted. Each function is checked in ``tests/benchmark`` against a
hand count at one tiny shape. FLOPs per trained token are a model
family's own count (``families/<model_type>.py:train_flops_per_token``): the
usual ``6`` per matmul weight a token meets (2 forward, 4 backward) plus
the attention term below. Activation recomputation is work the hardware
does and the model does not need: NOT counted.
"""
import json
from pathlib import Path


def peaks(device_kind, table=None):
    """Peak rates of ``device_kind`` from ``peaks.json``; an unknown device
    raises (a borrowed peak would put a wrong MFU under a right name)."""
    if table is None:
        with open(Path(__file__).with_name("peaks.json")) as f:
            table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in peaks.json (have "
            f"{sorted(k for k in table if not k.startswith('_'))}): add the "
            f"device with its source, do not borrow another's")
    return table[device_kind]


def program_bytes(compiled):
    """Device bytes a compiled program needs while it runs: arguments +
    results + temporaries, less what is aliased (``memory_analysis()``; the
    allocator's peak counts live arrays only, not these temporaries)."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def causal_pairs(seq, window=None):
    """(query, key) pairs a causal attention over ``seq`` positions scores:
    position i sees ``min(i + 1, window)`` keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_params(arch):
    """Weights of one layer's attention projections (q, k, v, o)."""
    d = arch["hidden_size"]
    q = arch["num_heads"] * arch["head_dim"]
    kv = arch["num_kv_heads"] * arch["head_dim"]
    return d * q + 2 * d * kv + q * d


def attention_train_flops(arch, seq):
    """Forward + backward FLOPs per trained token of softmax attention's
    two products over the causal pairs, in every layer: ``4 * head_dim`` per
    pair and head forward, three times that with the backward."""
    pairs = causal_pairs(seq, arch.get("sliding_window"))
    return 3 * 4 * arch["head_dim"] * arch["num_heads"] \
        * arch["num_layers"] * pairs / seq


# ------------------------------------------------------- the flash kernels
# ops/flash_attention.py runs three Pallas kernels: the forward, and a
# backward split in two (dq; dk+dv), each of which recomputes the scores.
# Matrix products per (query, key) pair and head, each 2 * head_dim FLOPs:
FLASH_PRODUCTS = {"fwd": 2,   # q k^T, p v
                  "dq": 3,    # q k^T, do v^T, ds k
                  "dkv": 4}   # q k^T, do v^T, p^T do, ds^T q


def flash_flops(kernel, batch, heads, seq, head_dim, window=None):
    """FLOPs one call of flash ``kernel`` needs at these shapes (causal)."""
    return (2 * head_dim * FLASH_PRODUCTS[kernel] * batch * heads
            * causal_pairs(seq, window))


def flash_bytes(kernel, batch, heads, kv_heads, seq, head_dim, itemsize=2):
    """HBM bytes one call must move: every operand read once and every
    result written once (scores never leave the chip's fast memory). The
    log-sum-exp and delta rows are fp32, one per query and head."""
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    row = batch * heads * seq * 4
    if kernel == "fwd":      # read q k v; write o, lse
        return 2 * q + 2 * kv + row
    if kernel == "dq":       # read q k v do, lse, delta; write dq
        return 3 * q + 2 * kv + 2 * row
    if kernel == "dkv":      # read q k v do, lse, delta; write dk dv
        return 2 * q + 4 * kv + 2 * row
    raise KeyError(kernel)


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which limit sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
