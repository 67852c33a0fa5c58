"""The chunked retention's share of its roofline, over the traced
``ragged_forward`` rounds: the PIECES alone (the chunks of two tokens or
more, cut into runs of at most ``retention_chunk_size`` rows), not the
one-token rows that a mixed round carries beside them: those take the decode
step, whose time lies under ``ret_scan`` but outside ``ret_chunk`` and is
``ret_decode_roofline``'s to read in the decode rounds.

What no chunking can avoid, by ``flops.roofline_seconds``: the FLOPs of the
family's ``retention_chunk_flops`` (the quadratic part over the causal half,
``phi(Q) S`` only for a piece with a predecessor, the state's update always)
and the bytes of the pieces' rows in and out (``retention_row_bytes``) and of
every piece's state, read where it has a predecessor and written always, at
its LEAST size (``retention_state_bytes``: the distinct products, not the
program's layout). Of the ``round`` record: the pieces' rows are ``ret_rows
- decode_rows`` (a one-token chunk is a decode row), the pieces
``ret_pieces`` (summed over the layers, a one-token chunk one piece) less
``decode_rows`` x the layers, those that start a sequence ``ret_first``. The
pieces are taken as equal in rows, which is the least their quadratic part
can cost (it is convex in a piece's rows). Against the device time of the
operations under the ``ret_chunk`` scope inside each forward's execution: a
floor, it cannot pass 100.

Nothing to read, and ``None``: a family without the counts, an engine
without a state pool, records without ``ret_rows`` / ``ret_pieces``, a
program without the scope, a trace without a round that carried a piece."""
from benchmark import flops, scopes, spans


def pieces_of(record, layers):
    """``(rows, pieces, first)`` of the chunks of two tokens or more in the
    forward a ``round`` record launched: rows through EACH layer, pieces and
    those of them that start a sequence in ONE layer. None where the record
    lacks a count."""
    rows, pieces = record.get("ret_rows"), record.get("ret_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return (rows - ones, pieces // layers - ones,
            record.get("ret_first", 0) // layers)


def chunk_work(arch, family, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's pieces in all ``layers``:
    ``pieces`` pieces of ``rows / pieces`` rows each, ``first`` of them with
    no predecessor."""
    each = rows / pieces
    fl = (first * family.retention_chunk_flops(arch, each, True)
          + (pieces - first) * family.retention_chunk_flops(arch, each, False))
    state = family.retention_state_bytes(arch)
    by = rows * family.retention_row_bytes(arch) \
        + (2 * pieces - first) * state
    return layers * fl, layers * by


def read(obs):
    family = obs["family"]
    if not hasattr(family, "retention_chunk_flops"):
        return None
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("ret_chunk",))
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
