"""The delta-rule state update's share of its memory roofline, over the
traced ``decode_forward`` rounds: what a decode step of a model with
delta-rule layers cannot avoid, whoever implements the update: every live
sequence's state in every such layer read once and written once
(``kda_pieces`` of the program's ``round`` record: the pieces whose state was
read and written, summed over those layers; x 2 x a slot-layer's bytes AS THE
ENGINE HOLDS THEM, ``engine.state_stats()``: the float32 state and the
convolution's tail) over the HBM bandwidth, against the device time of the
operations under the ``kda_conv`` and ``kda_step`` scopes (the state step's
Pallas call among them, by its name) inside each forward's execution. The
rows' own activations are not counted: a floor, it cannot pass 100 even when
every slot is live.

Nothing to read, and ``None``: an engine without ``state_stats()`` or a
model without state, records without ``kda_pieces``, a program without the
scopes, a trace without such a round."""
from benchmark import scopes, spans
# the bytes of a slot-layer as the engine holds them and the least time to
# read and write them: the same for every kind of state
from benchmark.metrics.ret_decode_roofline import (slot_layer_bytes,
                                                   state_seconds)

SCOPES = ("kda_conv", "kda_step")
KERNELS = (("kda_state_step", "kda_step"),)


def read(obs):
    per_piece = slot_layer_bytes(obs)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES, KERNELS)
    if not per_piece or not rounds or not ops:
        return None
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        pieces = d.get("kda_pieces")
        if d["program"] != "decode_forward" or not pieces:
            continue
        ran = dev.forward(d["program"], d["t0"], d["t1"])
        if not ran:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += state_seconds(pieces, per_piece, obs["peaks"])
        took += seconds
    return 100.0 * ideal / took if took else None
