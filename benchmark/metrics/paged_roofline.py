"""The paged decode kernel's share of its memory roofline, over the traced
``decode_forward`` rounds: the bytes the kernel cannot avoid reading — in
each of its calls of one execution (one per layer) every live sequence's
cached context (``ctx_tokens`` of the program's ``round`` record), K and V,
all KV heads at the pool's padded head size — over the HBM bandwidth,
against the device time those custom calls took. It reads whole blocks and
this counts tokens, so the share is a floor of what the kernel moves and
cannot pass 100. Bound by memory: a decode step's FLOPs are 2 per byte."""
from benchmark import spans


def read(obs):
    rounds = spans.traced_rounds(obs)
    if not rounds:
        return None
    _layers, _slots, kv_heads, head_dim = obs["engine"].kv.k.shape
    per_token = 2 * kv_heads * head_dim * obs["engine"].kv.k.dtype.itemsize
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        if d["program"] != "decode_forward":
            continue
        ran = dev.forward("decode_forward", d["t0"], d["t1"])
        if not ran:
            continue
        calls, seconds = dev.kernels_in(*ran)
        ideal += calls * d["ctx_tokens"] * per_token \
            / obs["peaks"]["hbm_bytes_per_s"]
        took += seconds
    return 100.0 * ideal / took if took else None
