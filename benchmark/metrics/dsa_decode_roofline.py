"""The one-token rows' attention's share of its memory roofline under a
sparse-attention indexer, over the traced rounds of EITHER program (every
row of a ``decode_forward``, the one-token chunks of a mixed
``ragged_forward``): the bytes it cannot avoid reading, K and V of every
SELECTED token (``dec_sel_tokens`` of the program's ``round`` record; 2,048
B a token and layer at 4 kv heads of 128 in bf16) in each layer, over the
HBM bandwidth, against the device time under the ``dsa_rows`` scope: the
gather of the selected rows through the block table and the attention over
them. What the indexer's scores and the selection cost is
``dsa_index_roofline``'s and ``dsa_select_share_pct``'s. A floor: it cannot
pass 100.

Nothing to read, and ``None``: a family without an indexer, a program whose
records lack ``dec_sel_tokens``, a trace without such a round."""
from benchmark import scopes, spans

SCOPES = ("dsa_rows",)


def read(obs):
    need = getattr(obs["family"], "selected_rows_bytes", None)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES)
    if need is None or not rounds or not ops:
        return None
    arch = obs["family"].arch(obs["config"])
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        tokens = d.get("dec_sel_tokens")
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not tokens or not ran:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += need(arch, tokens) / obs["peaks"]["hbm_bytes_per_s"]
        took += seconds
    return 100.0 * ideal / took if took else None
