"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the calls that ran (the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth, from the calls' shapes:
``benchmark.flops``) over the device time they took in the traced window.

The three kernels are told apart by what they return: forward ``(o, lse)``,
dq one array, dk+dv two arrays of one shape. At the cells' shapes every one
of them is bound by compute (``roofline_seconds`` says which)."""
import re

from benchmark import flops, trace

SHAPE = re.compile(r"[a-z]+\d+\[([\d,]*)\]")


def kernel_of(text):
    """``fwd`` | ``dq`` | ``dkv`` from a flash custom call's result type."""
    result = text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    shapes = SHAPE.findall(result)
    if len(shapes) == 1:
        return "dq"
    return "dkv" if shapes[0] == shapes[1] else "fwd"


def read(obs):
    tr = obs["trace"]
    if tr is None:
        return None
    lo, hi = obs["trace_window"]
    arch = obs["family"].arch(obs["config"])
    # per-device batch: the kernel sees its shard of the global batch
    batch = obs["config"]["train"]["batch"] // obs["chips"]
    ideal = took = 0.0
    for text, start, dur in trace.leaf_ops(tr, sorted(tr["devices"])[0]):
        if trace.op_kind(text) != "kernel" or not lo <= start <= hi:
            continue
        k = kernel_of(text)
        need = flops.flash_flops(k, batch, arch["num_heads"], obs["seq_len"],
                                 arch["head_dim"], arch["sliding_window"])
        moved = flops.flash_bytes(k, batch, arch["num_heads"],
                                  arch["num_kv_heads"], obs["seq_len"],
                                  arch["head_dim"])
        ideal += flops.roofline_seconds(need, moved, obs["peaks"])[0]
        took += dur
    return 100.0 * ideal / took if took else None
