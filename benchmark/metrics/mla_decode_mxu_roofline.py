"""The paged decode kernel's share of its ROOFLINE on a latent pool, compute
and memory both, over the traced rounds of either program (every row of a
``decode_forward``, the one-token chunks of a mixed ``ragged_forward``).

``mla_decode_roofline`` holds the kernel to the bytes alone, which is the
bound under 32 heads (54 FLOPs a byte). Every head reads the SAME cached
row, so the work a byte meets grows with the heads: in each of the kernel's
calls (one a layer) every one-token row's cached context
(``dec_ctx_tokens`` of the program's ``round`` record), a cached token
meeting ``heads x 2 x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)``
FLOPs (the score against the whole row, the value product over its latent
part) for the pool's row of bytes (lane-padded: 1,280 B). At 128 heads that
is 278.5 kFLOP a row, 218 FLOPs a byte against the chip's 240: the kernel
stands at the ridge, and the larger of the two times
(``flops.roofline_seconds``) is what it cannot beat. Against the device time
of the ``paged_decode`` custom calls. A floor (tokens, not the whole blocks
it reads; the row's useful lanes in the products), so it cannot pass 100.

Nothing to read, and ``None``: a family without latent attention, a program
whose records lack ``dec_ctx_tokens``, a trace without such a round."""
from benchmark import flops, scopes, spans

KERNEL = (("paged_decode", "mla_decode"),)


def decode_work(arch, calls, dec_ctx_tokens, row_bytes):
    """``(FLOPs, bytes)`` of ``calls`` calls of the kernel: each over the
    one-token rows' whole contexts, one cached row a token for all heads."""
    d_k = arch["kv_lora_rank"] + arch["qk_rope_head_dim"]
    per_token = arch["num_heads"] * 2 * (d_k + arch["kv_lora_rank"])
    return (calls * dec_ctx_tokens * per_token,
            calls * dec_ctx_tokens * row_bytes)


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
        ideal += flops.roofline_seconds(
            *decode_work(arch, len(calls), ctx, row_bytes), obs["peaks"])[0]
        took += sum(calls)
    return 100.0 * ideal / took if took else None
