"""What share of its context this traffic's attention reads under a
sparse-attention indexer: 100 x (``sel_pairs`` + ``dec_sel_tokens``) /
(``attn_pairs`` + ``dec_ctx_tokens``) over the window's forwards, all four
counted by the program on the host from the chunks alone
(``ragged.attention_work`` / ``selection_work``, on the ``round`` record).
100 while every context is within ``topk``. ``None`` where the records lack
the two selected counts (a program without an indexer)."""
from benchmark import spans


def read(obs):
    rounds = [d for d in spans.window_records(obs) or ()
              if "sel_pairs" in d]
    seen = sum(d.get("attn_pairs", 0) + d.get("dec_ctx_tokens", 0)
               for d in rounds)
    if not seen:
        return None
    return 100.0 * sum(d["sel_pairs"] + d.get("dec_sel_tokens", 0)
                       for d in rounds) / seen
