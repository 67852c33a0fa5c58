"""The chunked delta rule's share of its roofline, over the traced
``ragged_forward`` rounds: the PIECES alone (the chunks of two tokens or
more, cut into runs of at most ``kda_chunk_size`` rows), not the one-token
rows that a mixed round carries beside them: those take the state step, whose
time lies under ``kda_step`` and is ``kda_decode_roofline``'s to read in the
decode rounds.

What no chunking can avoid, by ``flops.roofline_seconds``: the recurrence's
own FLOPs by the SEQUENTIAL form (the family's ``kda_step_flops`` a row and
layer: the decay, ``k^T S``, the rank-one write, the read-out; a chunked form
does more and reads lower) and the bytes of the pieces' rows in and out
(``kda_row_bytes``) and of every piece's state (``kda_state_bytes``), read
where it has a predecessor and written always. Of the ``round`` record: the
pieces' rows are ``kda_rows - decode_rows`` (a one-token chunk is a decode
row), the pieces ``kda_pieces`` (summed over the layers, a one-token chunk
one piece) less ``decode_rows`` x the layers, those that start a sequence
``kda_first``. Against the device time of the operations under the
``kda_chunk`` scope inside each forward's execution: a floor, it cannot pass
100.

Nothing to read, and ``None``: a family without the counts, an engine
without a state pool, records without ``kda_rows`` / ``kda_pieces``, a
program without the scope, a trace without a round that carried a piece."""
from benchmark import flops, scopes, spans


def pieces_of(record, layers):
    """``(rows, pieces, first)`` of the chunks of two tokens or more in the
    forward a ``round`` record launched: rows through EACH layer, pieces and
    those of them that start a sequence in ONE layer. None where the record
    lacks a count."""
    rows, pieces = record.get("kda_rows"), record.get("kda_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return (rows - ones, pieces // layers - ones,
            record.get("kda_first", 0) // layers)


def chunk_work(arch, family, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's pieces in all ``layers``:
    ``rows`` rows in ``pieces`` pieces, ``first`` of them with no
    predecessor."""
    state = family.kda_state_bytes(arch)
    return (layers * rows * family.kda_step_flops(arch),
            layers * (rows * family.kda_row_bytes(arch)
                      + (2 * pieces - first) * state))


def read(obs):
    family = obs["family"]
    if not hasattr(family, "kda_step_flops"):
        return None
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("kda_chunk",))
    if not stats or not stats.get("layers") or not rounds or not ops:
        return None
    arch = family.arch(obs["config"])
    layers = stats["layers"]
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        rows, pieces, first = pieces_of(d, layers) or (0, 0, 0)
        if d["program"] != "ragged_forward" or rows <= 0 or pieces <= 0:
            continue
        ran = dev.forward(d["program"], d["t0"], d["t1"])
        if not ran:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(
            *chunk_work(arch, family, rows, pieces, first, layers),
            obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
