"""The chunked lightning attention's share of its roofline, over the traced
``ragged_forward`` rounds: the PIECES alone (the chunks of two tokens or
more, cut into runs of at most ``lightning_chunk_size`` rows), not the
one-token rows that a mixed round carries beside them: those take the state
step, whose time lies under ``la_step``.

What no chunking can avoid, by ``flops.roofline_seconds`` (as
``kda_chunk_roofline`` counts for the delta rule): the recurrence's own FLOPs
by the SEQUENTIAL form (the family's ``la_step_flops`` a row and layer: the
write and the read-out; a chunked form does more and reads lower) and the
bytes of the pieces' rows in and out (``la_row_bytes``) and of every piece's
state (``la_state_bytes``), read where it has a predecessor and written
always. Of the ``round`` record: the pieces' rows are ``la_rows -
decode_rows``, the pieces ``la_pieces`` (summed over the layers, a one-token
chunk one piece) less ``decode_rows`` x the layers, those that start a
sequence ``la_first``. Against the device time of the operations under the
``la_chunk`` scope inside each forward's execution: a floor, it cannot pass
100.

Nothing to read, and ``None``: a family without the counts, an engine
without a state pool, records without ``la_rows`` / ``la_pieces``, a program
without the scope, a trace without a round that carried a piece."""
from benchmark import flops, scopes, spans


def chunk_work(arch, family, rows, pieces, first, layers):
    """``(FLOPs, bytes)`` of one forward's pieces in all ``layers``:
    ``rows`` rows in ``pieces`` pieces, ``first`` of them with no
    predecessor."""
    state = family.la_state_bytes(arch)
    return (layers * rows * family.la_step_flops(arch),
            layers * (rows * family.la_row_bytes(arch)
                      + (2 * pieces - first) * state))


def read(obs):
    family = obs["family"]
    if not hasattr(family, "la_step_flops"):
        return None
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("la_chunk",))
    if not stats or not stats.get("layers") or not rounds or not ops:
        return None
    arch = family.arch(obs["config"])
    layers = stats["layers"]
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        ones = d.get("decode_rows")
        if d["program"] != "ragged_forward" or ones is None \
                or "la_rows" not in d or "la_pieces" not in d:
            continue
        rows = d["la_rows"] - ones
        pieces = d["la_pieces"] // layers - ones
        ran = dev.forward(d["program"], d["t0"], d["t1"])
        if rows <= 0 or pieces <= 0 or not ran:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(
            *chunk_work(arch, family, rows, pieces,
                        d.get("la_first", 0) // layers, layers),
            obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
