"""The paged decode kernel's share of its memory roofline on a latent pool,
over the traced rounds of EITHER program (every row of a ``decode_forward``,
the one-token chunks of a mixed ``ragged_forward``): the bytes the kernel
cannot avoid reading, in each of its calls of one execution (one a layer)
every one-token row's cached context (``dec_ctx_tokens`` of the program's
``round`` record) at the pool's row (the latent and the shared key, padded
to the lanes: 1,280 B) once, for all heads, over the HBM bandwidth, against
the device time of the ``paged_decode`` custom calls. It reads whole blocks
and this counts tokens, so the share is a floor and cannot pass 100. Bound
by memory: under 32 heads a cached row of 1,280 B meets ~70 kFLOP, 54 FLOPs
a byte against the chip's 240.

Nothing to read, and ``None``: a family without latent attention, a program
whose records lack ``dec_ctx_tokens``, a trace without such a round."""
from benchmark import scopes, spans

KERNEL = (("paged_decode", "mla_decode"),)


def decode_bytes(calls, dec_ctx_tokens, row_bytes):
    """Bytes ``calls`` calls of the kernel must read: each the one-token
    rows' whole contexts, one cached row a token."""
    return calls * dec_ctx_tokens * row_bytes


def read(obs):
    arch = obs["family"].arch(obs["config"])
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("mla_decode",), KERNEL)
    if "kv_lora_rank" not in arch or not rounds or not ops:
        return None
    pool = obs["engine"].kv.k
    row_bytes = pool.shape[-1] * pool.dtype.itemsize
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        ctx = d.get("dec_ctx_tokens")
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not ctx or not ran:
            continue
        calls = [dur for _l, program, start, dur in ops
                 if program == d["program"] and ran[0] <= start < ran[1]]
        if not calls:
            continue
        ideal += decode_bytes(len(calls), ctx, row_bytes) \
            / obs["peaks"]["hbm_bytes_per_s"]
        took += sum(calls)
    return 100.0 * ideal / took if took else None
