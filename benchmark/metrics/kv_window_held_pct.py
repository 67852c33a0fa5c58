"""Of the live sequences' context, the share whose rows are RESIDENT in the
windowed layers' pool, over the window's forwards of a stack of two
attention kinds (``ModelConfig.attn_period``): 100 x ``kv_window_tokens`` /
``kv_live_ctx_tokens``, both counted by the program on the host before each
launch (the ``round`` record; this forward's tokens are in both). 100 =
nothing was ever given back (one pool under one table would read that);
a context many times the window reads ``(window + chunk) / context``.
``None`` where the records lack the counts (every model with one pool)."""
from benchmark import spans


def read(obs):
    rounds = [d for d in spans.window_records(obs) or ()
              if d.get("kv_live_ctx_tokens")]
    live = sum(d["kv_live_ctx_tokens"] for d in rounds)
    if not live:
        return None
    return 100.0 * sum(d["kv_window_tokens"] for d in rounds) / live
