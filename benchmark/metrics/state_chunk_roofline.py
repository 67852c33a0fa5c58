"""The chunked form's share of its roofline in a model with recurrent state,
over the traced ``ragged_forward`` rounds: the PIECES alone (the chunks of two
tokens or more, cut into runs of at most the layer's chunk size), not the
one-token rows that a mixed round carries beside them: those take the state
step, which is ``state_decode_roofline``'s to read in the decode rounds.

What no chunking can avoid, by ``flops.roofline_seconds``: the FLOPs and
bytes that the family's ``"recurrent_state"`` kind counts for a forward's
pieces from its ``round`` record (``chunk_work``: the recurrence's own FLOPs
for the pieces' rows, those rows in and out, every piece's state read where
it has a predecessor and written always; each family's module says what its
count holds and leaves out), against the device time of the operations under
the family's ``chunk_scopes`` inside each forward's execution: a floor, it
cannot pass 100.

Nothing to read, and ``None``: a family that says no such kind, an engine
without a state pool, records without the family's counts, a program without
the scope, a trace without a round that carried a piece."""
from benchmark import flops, scopes, spans
from benchmark.reference import layer_kind


def read(obs):
    kind = layer_kind(obs["family"], "recurrent_state")
    if kind is None:
        return None
    ops = scopes.scoped_ops(obs, kind["chunk_scopes"],
                            kind.get("chunk_kernels", ()))
    work = kind["chunk_work"](obs) if ops else None
    if work is None:
        return None

    def least_s(record):
        need = work(record)
        return need and flops.roofline_seconds(*need, obs["peaks"])[0]
    return spans.floor_share(obs, ops, "ragged_forward", least_s)
