"""What one cached token costs the indexer's pool: the bytes of the engine's
third pool array (``engine.kv.idx``: the sparse-attention indexer's one key a
token and layer) over its slots, all layers. 1,536 B for twelve layers of one
64-wide bf16 row (two slots a 128-wide row of the array), beside 24,576 B of K and V. Counted from the array's shape:
no transfer. ``None`` for a model without an indexer."""


def read(obs):
    kv = getattr(obs.get("engine"), "kv", None)
    idx = getattr(kv, "idx", None)
    if idx is None:
        return None
    return idx.size * idx.dtype.itemsize / kv.k.shape[1]
