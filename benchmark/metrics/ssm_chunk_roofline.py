"""The chunked scan's share of its roofline, over the traced
``ragged_forward`` rounds: the PIECES alone (the chunks of two tokens or
more, cut into runs of at most ``chunk_size`` rows), not the one-token rows
that a mixed round carries beside them: those take the decode step, whose
time lies under ``ssm_scan`` but outside ``ssm_chunk`` and is
``ssm_decode_roofline``'s to read in the decode rounds.

What no chunking can avoid, by ``flops.roofline_seconds``: the recurrence's
own FLOPs for the pieces' rows (the family's ``ssm_scan_flops``: 6 x H x P x
N a row and Mamba layer), the bytes of those rows in and out of the scan
(``ssm_row_bytes``) and every piece's SSM state read and written once, as
the engine holds it (``engine.kv.ssm``; the convolution's tail is
``ssm_conv``'s, not counted). Of the ``round`` record: the pieces' rows are
``ssm_rows - decode_rows`` (a one-token chunk is a decode row), the pieces
``ssm_pieces`` (summed over the Mamba layers, a one-token chunk one piece)
less ``decode_rows`` x those layers. Against the device time of the
operations under the ``ssm_chunk`` scope inside each forward's execution.
The quadratic form inside a piece spends more FLOPs than the recurrence
needs and is not counted: a floor, it cannot pass 100.

Nothing to read, and ``None``: a family without the two counts, an engine
without a state pool, records without ``ssm_rows`` / ``ssm_pieces``, a
program without the scope, a trace without a round that carried a piece."""
import math

from benchmark import flops, scopes, spans


def state_bytes(obs):
    """One sequence's SSM state in ONE Mamba layer as the engine holds it,
    or None."""
    pool = getattr(getattr(obs.get("engine"), "kv", None), "ssm", None)
    return None if pool is None \
        else math.prod(pool.shape[2:]) * pool.dtype.itemsize


def pieces_of(record, layers):
    """``(rows, pieces)`` of the chunks of two tokens or more in the forward
    a ``round`` record launched: rows through EACH Mamba layer, pieces
    summed over the ``layers`` of them. None where the record lacks a
    count."""
    rows, pieces = record.get("ssm_rows"), record.get("ssm_pieces")
    ones = record.get("decode_rows")
    if rows is None or pieces is None or ones is None:
        return None
    return rows - ones, pieces - ones * layers


def scan_work(arch, family, rows, pieces, per_piece, layers):
    """``(FLOPs, bytes)`` of one forward's pieces: ``rows`` rows through
    each of ``layers`` Mamba layers, ``pieces`` state pieces (summed over
    the layers already) of ``per_piece`` bytes each."""
    return (rows * layers * family.ssm_scan_flops(arch),
            rows * layers * family.ssm_row_bytes(arch)
            + 2 * pieces * per_piece)


def read(obs):
    family = obs["family"]
    if not hasattr(family, "ssm_scan_flops"):
        return None
    per_piece = state_bytes(obs)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("ssm_chunk",))
    if not per_piece or not rounds or not ops:
        return None
    arch = family.arch(obs["config"])
    layers = family.layer_counts(arch)["M"]
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        rows, pieces = pieces_of(d, layers) or (0, 0)
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
            *scan_work(arch, family, rows, pieces, per_piece, layers),
            obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
