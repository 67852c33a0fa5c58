"""The recurrent state update's share of its memory roofline, over the
traced ``decode_forward`` rounds: what a decode step of a model with
recurrent state cannot avoid, whoever implements the update: every live
sequence's state in every state layer read once and written once (the
pieces whose state was read and written, summed over those layers: the
field of the program's ``round`` record that the family's
``"recurrent_state"`` kind names, ``step_pieces``; x 2 x a slot-layer's
bytes AS THE ENGINE HOLDS THEM: the family's ``slot_layer_bytes``, else
``engine.state_stats()``'s slot over its layers) over the HBM bandwidth,
against the device time of the operations under the family's ``step_scopes``
(a Pallas state step among them by its name, ``step_kernels``) inside each
forward's execution. The rows' own activations are not counted: a floor, it
cannot pass 100 even when every slot is live.

Nothing to read, and ``None``: a family that says no such kind, an engine
without ``state_stats()`` or a model without state, records without the
field, a program without the scopes, a trace without such a round."""
from benchmark import scopes, spans
from benchmark.reference import layer_kind


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
    kind = layer_kind(obs["family"], "recurrent_state")
    if kind is None:
        return None
    per_piece = kind.get("slot_layer_bytes", slot_layer_bytes)(obs)
    ops = scopes.scoped_ops(obs, kind["step_scopes"],
                            kind.get("step_kernels", ()))
    if not per_piece or not ops:
        return None
    return spans.floor_share(
        obs, ops, "decode_forward",
        lambda d: state_seconds(d.get(kind["step_pieces"]) or 0, per_piece,
                                obs["peaks"]))
