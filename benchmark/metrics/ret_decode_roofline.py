"""The retention state update's share of its memory roofline, over the
traced ``decode_forward`` rounds: what a decode step of a model with
power-retention layers cannot avoid, whoever implements the update: every
live sequence's state in every layer read once and written once
(``ret_pieces`` of the program's ``round`` record: the pieces whose state
was read and written, summed over the layers; x 2 x a slot-layer's bytes AS
THE ENGINE HOLDS THEM, ``engine.state_stats()``: ``S`` and ``z`` in their
dtype and the program's own layout) over the HBM bandwidth, against the
device time of the operations under the ``ret_scan`` scope inside each
forward's execution. The rows' own activations and their expanded features
are not counted: a floor, it cannot pass 100 even when every slot is live.

Nothing to read, and ``None``: an engine without ``state_stats()`` or a
model without state, records without ``ret_pieces``, a program without the
scope, a trace without such a round."""
from benchmark import scopes, spans

SCOPES = ("ret_scan",)


def slot_layer_bytes(obs):
    """Bytes of one sequence's state in ONE layer as the engine holds it,
    or None."""
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    if not stats or not stats.get("layers"):
        return None
    return stats["bytes_per_slot"] / stats["layers"]


def state_seconds(pieces, per_piece, peaks):
    """Least time ``pieces`` state pieces take: each read and written."""
    return 2 * pieces * per_piece / peaks["hbm_bytes_per_s"]


def read(obs):
    per_piece = slot_layer_bytes(obs)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES)
    if not per_piece or not rounds or not ops:
        return None
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        pieces = d.get("ret_pieces")
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
