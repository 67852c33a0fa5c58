"""Ragged-batch state: block allocator, sequence descriptors, batch metadata.

Analogs of the reference's ``inference/v2/ragged/`` host-side machinery:

* :class:`BlockedAllocator` — ``ragged/blocked_allocator.py`` free-list of KV
  blocks (there a torch int32 linked list; here a plain Python free list — this
  is host bookkeeping, never on device).
* :class:`SequenceDescriptor` — ``ragged/sequence_descriptor.py``
  (``DSSequenceDescriptor``): tokens seen/scheduled, owned KV blocks.
* :class:`RaggedBatch` — ``ragged/ragged_wrapper.py`` (``RaggedBatchWrapper``):
  the per-forward metadata arrays, built once on host and shipped to device as
  one transfer (the reference stages the same arrays into pinned host buffers).

Static shapes: every array is padded to (max_tokens, max_sequences,
blocks_per_seq) so ONE compiled XLA program serves every batch composition —
the TPU equivalent of the reference building variable-size batches eagerly.
"""
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class BlockedAllocator:
    """Refcounted KV block free-list (reference ``ragged/blocked_allocator.py``
    plus vLLM-style per-block reference counts for cross-request sharing).

    The serving loop (the scheduler's chunk admission) goes through
    :meth:`try_allocate`: exhaustion — real or injected
    (``DSTPU_FAULT_INJECTION`` ``kv_alloc_fail``) — answers ``None`` so the
    engine surfaces structured backpressure (the sequence stays pending)
    instead of an exception tearing down the whole serving loop.
    :meth:`allocate` keeps the raising contract for callers that pre-checked.

    Sharing contract (prefix cache, docs/serving.md "prefix reuse"): a
    freshly allocated block has refcount 1; every additional holder
    (another stream's block table, the prefix index's pin) must
    :meth:`retain` it, and every holder releases through
    :meth:`release`/:meth:`free` — the block returns to the free list only
    when its LAST holder lets go, so eviction/preempt/failover all route
    through the same refcounted release and can never tear a shared block
    out from under a live stream. ``reclaim_cb`` (installed with the
    prefix cache) is the pressure valve: a shortfall asks the cache to
    unpin cold unshared blocks before the allocator reports exhaustion.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError("need at least one block")
        self._free: List[int] = list(range(num_blocks))
        self._refs: List[int] = [0] * num_blocks
        self.num_blocks = num_blocks
        # pressure hook: called with the block shortfall before allocation
        # fails; returns how many blocks it freed (prefix_cache.reclaim)
        self.reclaim_cb = None

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def logical_blocks(self) -> int:
        """Sum of refcounts: block-table entries across all holders. With
        sharing this exceeds the physical ``num_blocks - free_blocks``."""
        return sum(self._refs)

    @property
    def shared_blocks(self) -> int:
        """Physical blocks with more than one holder."""
        return sum(1 for r in self._refs if r > 1)

    def refcount(self, block: int) -> int:
        if not 0 <= block < self.num_blocks:
            raise ValueError(f"refcount of invalid block {block}")
        return self._refs[block]

    def _relieve(self, n: int) -> None:
        if n > len(self._free) and self.reclaim_cb is not None:
            self.reclaim_cb(n - len(self._free))

    def try_allocate(self, n: int) -> Optional[List[int]]:
        """``allocate`` that reports exhaustion (or an injected allocation
        fault) as ``None`` instead of raising — the serving engine's
        backpressure seam."""
        self._relieve(n)
        if n > len(self._free):
            return None
        if n > 0:
            from ...utils.fault_injection import get_fault_injector

            if get_fault_injector().should_fail_kv_alloc():
                return None
        out, self._free = self._free[:n], self._free[n:]
        for b in out:
            self._refs[b] = 1
        return out

    def allocate(self, n: int) -> List[int]:
        self._relieve(n)
        if n > len(self._free):
            raise RuntimeError(
                f"KV cache exhausted: want {n} blocks, {len(self._free)} free")
        out, self._free = self._free[:n], self._free[n:]
        for b in out:
            self._refs[b] = 1
        return out

    def retain(self, blocks: Sequence[int]) -> None:
        """Add one holder to each LIVE block (mapping a cached prefix into
        a new stream's block table; pinning a block into the prefix
        index). Retaining a free block is a bug — it would resurrect
        storage another allocation may already own."""
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"retaining invalid block {b}")
            if self._refs[b] < 1:
                raise ValueError(f"retain of free block {b}")
        for b in blocks:
            self._refs[b] += 1

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one holder per block; a block returns to the free list only
        at refcount zero. Releasing a free block raises — double free is
        impossible by construction, shared or not."""
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"freeing invalid block {b}")
            if self._refs[b] < 1:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)

    # the reference's name; every legacy caller (flush/preempt/failover)
    # routes through the refcounted release
    free = release


class WindowedAllocator:
    """The block allocators of a stack of two attention kinds
    (``ModelConfig.attn_period``): ``full``, the pool whose rows live as
    long as their context, and ``window``, the windowed layers' pool, whose
    blocks a sequence gives back as its queries move past them
    (:meth:`SequenceDescriptor.out_of_window`). One discipline, two free
    lists: a chunk is admitted with a block of EACH for every new logical
    block (``scheduler._admit``), and a sequence that ends, is evicted or
    requeued gives both lists back. What this object answers itself is over
    BOTH pools: a block held at idle is a leak whichever pool holds it."""

    def __init__(self, full: BlockedAllocator, window: BlockedAllocator):
        self.full, self.window = full, window

    @property
    def num_blocks(self) -> int:
        return self.full.num_blocks + self.window.num_blocks

    @property
    def free_blocks(self) -> int:
        return self.full.free_blocks + self.window.free_blocks


class LogitsRef(NamedTuple):
    """Where a drained sequence's last-token logits are: row ``slot`` of the
    ``[max_sequences, V]`` array ONE forward returned. The slot is the
    sequence's place in THAT forward's chunks (it changes from forward to
    forward); the row is cut out only for a caller that reads it
    (``InferenceEngineV2.query``), the sampler gathers by slot."""
    array: Any   # the forward's whole logits, on the device
    slot: int


def device_token(row: int) -> int:
    """What stands in ``SequenceDescriptor.pending`` for a token whose VALUE
    is still on the device: row ``row`` of a sampler output that was
    launched and not read back (``engine_v2.SampledTokens``). Negative, as
    no token id is; a forward that eats it selects the value on the device
    (:func:`split_device_tokens`), and the read-back writes the value over
    whatever reference no forward ate."""
    return -(row + 1)


def split_device_tokens(tokens: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A forward's token vector as its two operands: the host's tokens with
    0 where a :func:`device_token` stood, and ``take_from``, that
    reference's row of the sampler's output (-1: the host's token)."""
    on_device = tokens < 0
    return (np.where(on_device, 0, tokens).astype(np.int32),
            np.where(on_device, -tokens - 1, -1).astype(np.int32))


@dataclass(eq=False)  # identity semantics: descriptors live in scheduler sets
class SequenceDescriptor:
    """Per-sequence serving state (reference ``DSSequenceDescriptor``)."""

    uid: int
    pending: List[int] = field(default_factory=list)  # tokens awaiting forward
    #                             (a lone negative one: device_token)
    n_cached: int = 0                                 # tokens with KV in cache
    blocks: List[int] = field(default_factory=list)   # owned KV block ids
    last_logits: Optional[LogitsRef] = None           # set when pending drains
    # --- prefix-cache state (inference/v2/prefix_cache.py) ---------------
    cached_prefix_len: int = 0  # tokens adopted from the prefix cache at
    #                             admission (block-aligned; positions/
    #                             sampling stay exact because token_pos
    #                             continues from n_cached)
    history: List[int] = field(default_factory=list)  # tokens committed to
    #                             KV, in position order (prefix-hash input)
    block_hashes: List[bytes] = field(default_factory=list)  # chained hash
    #                             per FULL block (prefix-trie keys)
    last_scheduled: int = -1   # engine forward-tick of the last chunk (LRU
    #                            eviction + prefill round-robin fairness)
    # --- SLA budget (serving.py admission gate / scheduler slack ordering).
    # All timestamps share one monotonic clock base (time.perf_counter by
    # default — the session's ``clock``); absolute wall time never enters.
    arrival_s: float = 0.0          # when the request was submitted
    deadline_s: Optional[float] = None  # absolute TTFT deadline (None = no SLA)
    rate_sla: float = 0.0           # required decode tokens/s (0 = none)
    tenant: str = "default"         # fairness-budget key
    target_new_tokens: int = 0      # requested generation length
    emitted: int = 0                # decode tokens delivered so far
    first_token_s: Optional[float] = None  # when the first token landed
    last_service_s: float = -1.0    # clock stamp of the last scheduled chunk
    #                                 (starvation aging in slack ordering)
    # a model with recurrent state (Mamba-2 or power-retention layers): the
    # sequence's place in the state pool (``kv_cache.BlockedKV.state``) from
    # its first token to its flush or eviction; None for every other model
    state_slot: Optional[int] = None
    # False: no layer of the model caches a key (``ModelConfig.num_kv_layers``
    # 0), so the sequence needs no block whatever its context
    caches_kv: bool = True
    # a stack of two attention kinds (``ModelConfig.attn_period``): the
    # sequence's blocks in the WINDOWED layers' pool, by logical position as
    # ``blocks`` is; the first ``window_freed`` were given back
    # (:meth:`out_of_window`), their entries read 0 and no kernel
    # dereferences them. None for every other model
    window_blocks: Optional[List[int]] = None
    window_freed: int = 0

    @property
    def needs_tokens(self) -> int:
        return len(self.pending)

    @property
    def window_held(self) -> List[int]:
        """The window-pool blocks the sequence still holds."""
        return (self.window_blocks or [])[self.window_freed:]

    def out_of_window(self, window: int, block_size: int) -> List[int]:
        """The window-pool blocks that NO query of the sequence can see any
        more, taken off its list (their entries become 0) for the caller to
        release. The next query stands at ``n_cached`` and sees key ``j``
        iff ``n_cached - j < window``; a block whose LAST token is at or
        below ``n_cached - window`` is dead for it and for every later one.
        The kernels skip exactly those blocks (``paged_attention.tile_span``:
        ``lo_blk = (pos0 + 1 - window) // block_size`` with ``pos0 >=
        n_cached``), so a freed entry is never read."""
        dead = min(max(0, (self.n_cached - window + 1) // block_size),
                   len(self.window_blocks))
        out = self.window_blocks[self.window_freed:dead]
        self.window_blocks[self.window_freed:dead] = [0] * len(out)
        self.window_freed = max(self.window_freed, dead)
        return out

    def blocks_needed(self, new_tokens: int, block_size: int) -> int:
        if not self.caches_kv:
            return 0
        total = self.n_cached + new_tokens
        want = -(-total // block_size)  # ceil
        return max(0, want - len(self.blocks))


def tile_places(rows: int, max_tokens: int, max_sequences: int,
                tile: int) -> int:
    """Places a forward of ``rows`` rows has for single-sequence tiles of
    ``tile`` rows: the attention's atoms (the last place reserved dead) and
    the state layers' pieces. The largest forward, ``max_tokens`` rows, must
    hold whatever the scheduler emits: a tile more than its whole ones for
    each of ``max_sequences`` chunks. A smaller one has room for three chunk
    tails beside its whole tiles; a round with more takes the next shape."""
    if rows >= max_tokens:
        return max_sequences + max_tokens // tile + 1
    return rows // tile + 4


class RaggedShape(NamedTuple):
    """One static shape of ``ragged_forward``: ``rows`` of the packed token
    axis, ``atoms`` of the ragged kernel's grid (0: the attention takes
    none) and ``pieces`` of the state layers' chunked form (0: the model has
    none), chosen together so that shapes do not multiply."""
    rows: int
    atoms: int
    pieces: int

    def holds(self, lengths: Sequence[int], atom_q: int, chunk: int) -> bool:
        """Whether chunks of ``lengths`` tokens fit: by their rows, by the
        atoms of ``atom_q`` rows their longer ones are cut into, and by the
        pieces of ``chunk`` rows."""
        return sum(lengths) <= self.rows and all(
            sum(-(-n // tile) for n in lengths if n > 1) <= places - 1
            for places, tile in ((self.atoms, atom_q), (self.pieces, chunk))
            if places)


def ragged_shapes(max_tokens: int, max_sequences: int, atom_q: int = 0,
                  chunk: int = 0) -> Tuple[RaggedShape, ...]:
    """The static shapes a mixed round's batch is built at, smallest first:
    ``max_tokens`` rows (the scheduler's budget: it holds any round, at the
    worst-case atom count) and, where that is smaller, its quarter rounded
    up to whole 128 rows, so a budget of 128 rows or fewer has ONE shape. A
    round runs at the first that :meth:`RaggedShape.holds` it: 31 decode
    rows and a 100-token prompt then cost a 256-row forward with 6 atoms,
    not 768 rows and 39. Two shapes and not more: every shape is one more
    program for ``warmup()`` to load (about a second each on the chip)."""
    quarter = -(-max_tokens // 512) * 128
    rows = (quarter, max_tokens) if quarter < max_tokens else (max_tokens,)

    def places(r, tile):
        return tile_places(r, max_tokens, max_sequences, tile) if tile else 0

    return tuple(RaggedShape(r, places(r, atom_q), places(r, chunk))
                 for r in rows)


class SsmBatch(NamedTuple):
    """What the state layers of one ``ragged_forward`` take (Mamba-2:
    ``ops/ssm.py``; power retention: ``ops/retention.py``). A chunk's place
    in the forward changes from forward
    to forward; its sequence's state does not move: ``seq_slot`` [S] is
    each chunk's state slot (the sink, ``S``, for an empty place). The
    one-token chunks are ``dec_row`` / ``dec_len`` as the attention's (kept
    here too: those are built only for an attention that takes atoms). The
    chunks of two tokens or more are cut into PIECES of at most ``chunk``
    consecutive rows, live ones first, in the order of the flat axis (so a
    sequence's pieces follow one another): ``row0`` first flat row,
    ``length`` rows (0 = dead), ``slot`` state slot, ``fresh`` whether the
    piece starts at position 0 (it then starts from zeros, whatever the slot
    holds); ``count`` the live pieces."""
    seq_slot: np.ndarray
    dec_row: np.ndarray
    dec_len: np.ndarray
    row0: np.ndarray
    length: np.ndarray
    slot: np.ndarray
    fresh: np.ndarray
    count: np.ndarray


def ssm_pieces(chunks, max_tokens: int, max_sequences: int,
               chunk: int, pieces: Optional[int] = None) -> SsmBatch:
    """:class:`SsmBatch` of scheduled ``(descriptor, n_tokens)`` chunks,
    laid on the flat axis as :func:`build_ragged_batch` lays them, with
    ``pieces`` places for them (None: the worst case of ``max_tokens`` rows,
    :func:`tile_places`)."""
    S = max_sequences
    p_max = pieces or tile_places(max_tokens, max_tokens, S, chunk)
    seq_slot = np.full((S,), S, np.int32)
    dec_row, dec_len = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    row0, length = np.zeros((p_max,), np.int32), np.zeros((p_max,), np.int32)
    slot, fresh = np.full((p_max,), S, np.int32), np.zeros((p_max,), bool)
    a = cur = 0
    for i, (desc, n) in enumerate(chunks):
        seq_slot[i] = desc.state_slot
        if n == 1:
            dec_row[i], dec_len[i] = cur, desc.n_cached + 1
        for k in range(0, n if n > 1 else 0, chunk):
            row0[a], length[a] = cur + k, min(chunk, n - k)
            slot[a], fresh[a] = desc.state_slot, desc.n_cached + k == 0
            a += 1
        cur += n
    return SsmBatch(seq_slot, dec_row, dec_len, row0, length, slot, fresh,
                    np.asarray(a, np.int32))


@dataclass
class RaggedBatch:
    """One forward's metadata (reference ``RaggedBatchWrapper``): flat token
    stream + per-token routing + per-sequence block tables. All padded."""

    tokens: np.ndarray        # [T] int32
    token_seq: np.ndarray     # [T] int32, slot id; padded entries = max_sequences
    token_pos: np.ndarray     # [T] int32 position within sequence
    block_tables: np.ndarray  # [S, blocks_per_seq] int32
    last_tok_idx: np.ndarray  # [S] int32 index into tokens of each slot's last chunk token
    seq_active: np.ndarray    # [S] bool
    uids: List[int]           # slot -> uid (host only)
    # atom decomposition (reference atom_builder, ragged_ops/): fixed-size
    # single-sequence q tiles of ``atom_q`` rows for the ragged
    # paged-attention kernel. Only a chunk of TWO tokens or more is cut into
    # atoms; a one-token chunk (a decode step in a mixed round, or a
    # one-token prompt: the same mathematics) is no atom but a row of the
    # per-slot vectors below, and attends through the kernel's one-row tile
    atom_qidx: Optional[np.ndarray] = None    # [A, BQ] packed-row gather idx
    atom_pos0: Optional[np.ndarray] = None    # [A] first q position
    atom_qlen: Optional[np.ndarray] = None    # [A] valid rows (0 = dead atom)
    atom_tables: Optional[np.ndarray] = None  # [A, Bps] owning block-table row
    atom_inv: Optional[np.ndarray] = None     # [T] packed row -> a*BQ + off
    #                   (padding and one-token rows: the reserved dead atom)
    # the one-token chunks, by slot (their tables are ``block_tables``)
    dec_row: Optional[np.ndarray] = None      # [S] packed row of the token
    dec_len: Optional[np.ndarray] = None      # [S] sequence length WITH that
    #                   token (0 = this slot has no one-token chunk)
    # a stack of two attention kinds: the sequences' tables into the
    # WINDOWED layers' pool, as ``block_tables`` / ``atom_tables`` (a freed
    # entry reads 0); None for every other model
    window_tables: Optional[np.ndarray] = None       # [S, blocks_per_seq]
    atom_window_tables: Optional[np.ndarray] = None  # [A, blocks_per_seq]

    @property
    def window_args(self) -> Tuple[np.ndarray, ...]:
        """The window tables as ``ragged_forward`` takes them behind its
        other operands; empty for a model with one pool."""
        if self.window_tables is None:
            return ()
        return (self.window_tables, self.atom_window_tables)

    @property
    def current_tokens(self) -> int:
        return int((self.token_seq < len(self.seq_active)).sum())

    @property
    def live_atoms(self) -> int:
        """Atoms with at least one row (0 for a batch built without atoms)."""
        return 0 if self.atom_qlen is None else \
            int(np.count_nonzero(self.atom_qlen))

    @property
    def tile_args(self) -> Tuple[np.ndarray, ...]:
        """The atoms and the one-token rows in the order ``ragged_forward``
        takes them after ``last_tok_idx``; empty for a batch built without
        atoms (the attention impls that cost a row per token anyway)."""
        if self.atom_qidx is None:
            return ()
        return (self.atom_qidx, self.atom_pos0, self.atom_qlen,
                self.atom_tables, self.atom_inv, self.dec_row, self.dec_len)


def attention_work(descs: Sequence[SequenceDescriptor],
                   lengths: Sequence[int], atom_q: int = 0,
                   step_keys: Tuple[int, int] = (1, 1)
                   ) -> Tuple[int, int, int, int]:
    """What the attention kernels of one forward over these chunks must
    cover, counted on the host from the chunks alone: ``attn_pairs``, the
    (row, cached token) pairs of the chunks of two tokens or more (the
    atoms' kernel; row r of a chunk that starts at position p0 attends
    p0 + r + 1 tokens, itself among them), and ``dec_ctx_tokens``, the
    context lengths of the one-token chunks, their own token included (the
    one-row tile's kernel reads that many cached rows a layer).

    Then what the kernels' loops walk for it, tile by tile, the atoms of
    ``atom_q`` rows (0: the attention takes none, and the longer chunks
    count nothing here) and the one-row tiles alike: ``kv_tile_keys``, the
    keys each tile may see (an atom's last row's position + 1; a one-row
    tile's context), and ``kv_step_keys``, those rounded up to the whole
    loop steps that cover them, ``step_keys`` = (an atom's, a one-row
    tile's) keys a step (``paged_attention.kv_step_keys``: by the tile's
    shape). Their ratio is what a step of several blocks pays at each
    sequence's tail. Each tile counts once, however many head tiles or
    layers read it again; a sliding window's skipped steps are not taken
    off."""
    pairs = sum(n * d.n_cached + n * (n + 1) // 2
                for d, n in zip(descs, lengths) if n > 1)
    ctx = sum(d.n_cached + 1 for d, n in zip(descs, lengths) if n == 1)
    tiles = [(d.n_cached + 1, step_keys[1])
             for d, n in zip(descs, lengths) if n == 1]
    if atom_q:
        tiles += [(d.n_cached + min(r + atom_q, n), step_keys[0])
                  for d, n in zip(descs, lengths) if n > 1
                  for r in range(0, n, atom_q)]
    return (pairs, ctx, sum(-(-hi // step) * step for hi, step in tiles),
            sum(hi for hi, _step in tiles))


def window_work(descs: Sequence[SequenceDescriptor], lengths: Sequence[int],
                window: int, atom_q: int) -> Tuple[int, int, int]:
    """What the atoms' kernels of a stack of two attention kinds
    (``ModelConfig.attn_period``) must cover in ONE windowed and ONE full
    layer, from the chunks alone (the chunks of two tokens or more; a
    one-token chunk is no atom): ``swa_pairs``, the (row, cached token)
    pairs INSIDE the window (the row at position p attends ``min(p + 1,
    window)``; the full layer's are :func:`attention_work`'s
    ``attn_pairs``); ``swa_atom_keys``, the keys each atom of ``atom_q``
    rows must read in a windowed layer, from its first row's window to its
    last row, summed over the atoms; ``full_atom_keys``, the same in a full
    layer, where an atom reads everything up to its last row. A kernel that
    walked a windowed layer's whole context would read more than this, and
    its share of the roofline less."""
    pairs = swa_keys = full_keys = 0
    for d, n in zip(descs, lengths):
        if n < 2:
            continue
        p0 = d.n_cached
        short = max(0, min(p0 + n, window) - p0)      # rows under the window
        pairs += (n - short) * window + short * p0 + short * (short + 1) // 2
        for r in range(0, n, atom_q or n):
            first, end = p0 + r, p0 + min(r + (atom_q or n), n)
            swa_keys += end - max(0, first - window + 1)
            full_keys += end
    return pairs, swa_keys, full_keys


def selection_work(descs: Sequence[SequenceDescriptor],
                   lengths: Sequence[int], topk: int,
                   walk: Tuple[int, int] = (1, 1)) -> Tuple[int, int, int]:
    """:func:`attention_work`'s two counts under a sparse-attention indexer
    that keeps the ``topk`` best cached tokens a row
    (``ModelConfig.index_topk``), from the chunks alone: ``sel_pairs``, the
    (row, SELECTED token) pairs of the chunks of two tokens or more (the row
    at position p attends ``min(p + 1, topk)``), and ``dec_sel_tokens``, the
    same over the one-token chunks. Beside ``attn_pairs`` and
    ``dec_ctx_tokens`` they say what share of its context the attention
    reads.

    Then what the indexer's two steps WALK for the one-token chunks,
    ``dec_walk_keys``: each row's context rounded up to the scores' step
    (a tile of its own), plus for every row the longest's rounded up to the
    selection's chunk (one tile walks them together); ``walk`` = (keys a
    scores step, keys a selection chunk) (``sparse_index.score_keys`` /
    ``select_chunk``; a route that takes no kernels walks the whole table:
    both the table's width). Over ``2 x rows x`` the table's width it is
    the share of the score matrix's columns still paid."""
    def kept(d, n):
        full = max(0, min(d.n_cached + n, topk) - d.n_cached)   # rows < topk
        return (n - full) * topk + full * d.n_cached + full * (full + 1) // 2

    rows = [d.n_cached + 1 for d, n in zip(descs, lengths) if n == 1]
    step, chunk = walk
    return (sum(kept(d, n) for d, n in zip(descs, lengths) if n > 1),
            sum(min(ctx, topk) for ctx in rows),
            sum(-(-ctx // step) * step for ctx in rows)
            + len(rows) * (-(-max(rows, default=0) // chunk) * chunk))


def build_ragged_batch(chunks: Sequence[Tuple[SequenceDescriptor, int]],
                       max_tokens: int, max_sequences: int,
                       blocks_per_seq: int,
                       atom_q: Optional[int] = None,
                       atoms: Optional[int] = None) -> RaggedBatch:
    """Assemble metadata for scheduled ``(descriptor, n_tokens)`` chunks.

    The chunk's tokens are ``desc.pending[:n_tokens]``; positions continue from
    ``desc.n_cached``. Mirrors ``RaggedBatchWrapper.insert_sequence`` +
    ``finalize``. ``max_tokens`` rows and ``atoms`` atoms (None: the worst
    case of that many rows) are the forward's static shape
    (:func:`ragged_shapes`): every array here is sized by them, and what
    they hold beyond the chunks is padding the model never reads.
    """
    if len(chunks) > max_sequences:
        raise ValueError(f"{len(chunks)} chunks > max_sequences {max_sequences}")
    T, S = max_tokens, max_sequences
    tokens = np.zeros((T,), np.int32)
    token_seq = np.full((T,), S, np.int32)   # S = padding sentinel
    token_pos = np.zeros((T,), np.int32)
    block_tables = np.zeros((S, blocks_per_seq), np.int32)
    last_tok = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    uids: List[int] = []
    windowed = any(d.window_blocks is not None for d, _n in chunks)
    window_tables = np.zeros((S, blocks_per_seq), np.int32) if windowed \
        else None

    cursor = 0
    for slot, (desc, n) in enumerate(chunks):
        assert n >= 1 and n <= len(desc.pending)
        if cursor + n > T:
            raise ValueError("token budget overflow — scheduler bug")
        tokens[cursor:cursor + n] = desc.pending[:n]
        token_seq[cursor:cursor + n] = slot
        token_pos[cursor:cursor + n] = np.arange(desc.n_cached,
                                                 desc.n_cached + n)
        block_tables[slot, :len(desc.blocks)] = desc.blocks
        if windowed:
            window_tables[slot, :len(desc.window_blocks)] = desc.window_blocks
        last_tok[slot] = cursor + n - 1
        active[slot] = True
        uids.append(desc.uid)
        cursor += n

    tiles = {} if not windowed else dict(window_tables=window_tables)
    if atom_q:
        # atoms: ≤atom_q-row single-sequence q tiles (reference atom_builder)
        # of the chunks of two tokens or more. Worst case sum(ceil(n_i/BQ))
        # ≤ S + T//BQ; slot A_max-1 is reserved DEAD (qlen 0) so padded
        # packed rows, and the one-token chunks' (dec_row / dec_len: a
        # whole atom would hold one live row), gather a guaranteed zero
        BQ = atom_q
        A_max = atoms or tile_places(T, T, S, BQ)
        atom_qidx = np.zeros((A_max, BQ), np.int32)
        atom_pos0 = np.zeros((A_max,), np.int32)
        atom_qlen = np.zeros((A_max,), np.int32)
        atom_tables = np.zeros((A_max, blocks_per_seq), np.int32)
        atom_inv = np.full((T,), (A_max - 1) * BQ, np.int32)
        atom_window_tables = np.zeros_like(atom_tables) if windowed else None
        dec_row = np.zeros((S,), np.int32)
        dec_len = np.zeros((S,), np.int32)
        a = 0
        cur = 0
        for slot, (desc, n) in enumerate(chunks):
            pos0 = desc.n_cached
            if n == 1:
                dec_row[slot] = cur
                dec_len[slot] = pos0 + 1
            else:
                for k in range(0, n, BQ):
                    ql = min(BQ, n - k)
                    rows = cur + k + np.arange(ql)
                    atom_qidx[a, :ql] = rows
                    atom_pos0[a] = pos0 + k
                    atom_qlen[a] = ql
                    atom_tables[a] = block_tables[slot]
                    if windowed:
                        atom_window_tables[a] = window_tables[slot]
                    atom_inv[rows] = a * BQ + np.arange(ql)
                    a += 1
            cur += n
        assert a <= A_max - 1, "atom overflow — builder bug"
        tiles.update(atom_qidx=atom_qidx, atom_pos0=atom_pos0,
                     atom_qlen=atom_qlen, atom_tables=atom_tables,
                     atom_inv=atom_inv, dec_row=dec_row, dec_len=dec_len,
                     atom_window_tables=atom_window_tables)
    return RaggedBatch(tokens, token_seq, token_pos, block_tables, last_tok,
                       active, uids, **tiles)
