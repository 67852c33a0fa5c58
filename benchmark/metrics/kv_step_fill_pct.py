"""How much of what the paged kernels' loop steps covered was context, over
the window's forwards of either program. The kernel walks a tile's keys in
steps of several KV blocks on a latent pool (one on a K-and-V pool: by the
tile's shape, ``ops/paged_attention._kv_pages_per_step``), and the last step
of a tile is paid whole: its DMAs, its ``q k^T`` and ``p v``, its softmax.
The program counts both on the host from the chunks alone
(``ragged.attention_work``, on the ``round`` record): ``kv_tile_keys``, the
keys each tile may see, summed over the atoms and the one-row tiles, and
``kv_step_keys``, those rounded up to each entry's whole steps. Their ratio
is what a wider step pays at each sequence's tail: near 100 where contexts
are many steps long, and falling where the step was pushed too far for the
contexts the traffic brings.

Nothing to read, and ``None``: a program whose records lack the fields
(every commit before the one that added them), a window in which no forward
walked a step."""
from benchmark import spans


def read(obs):
    keys = steps = 0
    for d in spans.window_records(obs) or ():
        if d["program"]:
            keys += d.get("kv_tile_keys", 0)
            steps += d.get("kv_step_keys", 0)
    return 100.0 * keys / steps if steps else None
