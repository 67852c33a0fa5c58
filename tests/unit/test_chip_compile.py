"""Compile the main path's Pallas kernels for a *described* TPU v5e.

Interpret mode (the rest of the suite) proves numerics, and ``jax.export``
(``test_tpu_lowering.py``) proves the Pallas→Mosaic lowering — neither runs
the chip's compiler, which is what refuses a kernel for VMEM it may not use
or a slice off the tiling. libtpu compiles for a chip that is described and
not attached, so these cases hold every later PR to "the kernels of the
train and serve paths compile at real widths" at no chip time.

Only ONE process may load libtpu, so: this is the only file that describes
a topology, it does so inside a module-scoped fixture (never at import,
never autouse), and it compiles in the test's own process.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeedsyclsupport_tpu.inference.v2.config import RaggedInferenceConfig
from deepspeedsyclsupport_tpu.models import get_config
from deepspeedsyclsupport_tpu.ops.flash_attention import flash_attention
from deepspeedsyclsupport_tpu.ops.paged_attention import (
    paged_decode_attention_pallas, ragged_prefill_attention_pallas)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu / lock held by another process
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _once(compile_case):
    """A case's compiled program, kept at module scope: the cases below that
    inspect the same program's text compile it once (the sharding is the
    module's one; the widths are the case's own)."""
    return functools.lru_cache(maxsize=None)(compile_case)


def _compile(f, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(f).lower(*args).compile()


def _widths(name):
    """(heads, kv heads, head dim as the kernel sees it) of a preset."""
    cfg = get_config(name)
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def _serving_params(model, one_chip):
    """The abstract tree the engine hands its forwards: every floating leaf
    in the serving dtype (``jax.eval_shape(model.init_params)`` gives
    float32), in the layout the forwards take (``model.serving_layout``, on
    shapes)."""
    from deepspeedsyclsupport_tpu.inference.v2.model import serving_layout

    return serving_layout(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if jnp.issubdtype(x.dtype, jnp.floating)
            else x.dtype, sharding=one_chip),
        jax.eval_shape(model.init_params)), model.config)


@_once
def _cell_forward(one_chip, config, program):
    """``program`` of the benchmark's configuration ``config`` WHOLE, at the
    shapes its cell's engine gives it (the pool, the largest mixed round,
    the state's pieces, the sampler's tail), compiled for the described
    chip with the platform's kernels (no chip is attached: the registry and
    the grouped GEMM would hand their XLA forms). -> ``(compiled, the
    abstract pool, the abstract weights)``; compiled once a module."""
    from unittest import mock

    from benchmark import spec
    from deepspeedsyclsupport_tpu.comm import topology as topo_mod
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import init_blocked_kv
    from deepspeedsyclsupport_tpu.inference.v2.ragged import (SsmBatch,
                                                              ragged_shapes)
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg
    from deepspeedsyclsupport_tpu.ops.paged_attention import default_atom_rows

    cfg = spec.Bench().config(config)
    model = build_model(cfg["preset"], **cfg["overrides"], dtype=cfg["dtype"])
    mc = model.config
    eng = RaggedInferenceConfig.from_config(
        None, dtype=cfg["dtype"], head_dim_lane_pad=128, **cfg["engine"])

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    class NoMesh:           # what the pool asks of a topology: one device
        axis_sizes = {"model": 1}

        @staticmethod
        def replicated():
            return None

    kv = jax.tree_util.tree_map(
        lambda x: on_chip(x.shape, x.dtype),
        jax.eval_shape(lambda: init_blocked_kv(mc, eng, NoMesh)))
    params = _serving_params(model, one_chip)
    bs, seqs, toks, bps = (eng.block_size, eng.max_sequences,
                           eng.max_tokens_per_batch, eng.blocks_per_seq)
    tail = kv.exit_pass if kv.exit_pass is not None else kv.bsa
    n_tail = tail.size if tail is not None else 0 if kv.moe is None else sum(
        x is not None for x in (kv.moe.touched, kv.moe.tiles, kv.moe.rows))
    sampled = on_chip((seqs + n_tail,))
    windowed = mc.period_window is not None
    if program == "decode_forward":
        fn = M.build_decode_forward_fn(model, bs, "pallas")
        args = [on_chip((seqs,)), on_chip((seqs,)), on_chip((seqs, bps)),
                on_chip((seqs,), jnp.bool_), sampled, on_chip((seqs,))]
        state = [on_chip((seqs,))] if kv.state else []
        window = [on_chip((seqs, bps))] if windowed else []
    else:
        fn = M.build_ragged_forward_fn(model, bs, "kernel")
        # (a latent pool has no head axis: one kv head, as the engine says)
        atom = default_atom_rows(eng.atom_q_size, mc.num_heads,
                                 1 if kv.v is None else kv.k.shape[-2],
                                 kv.k.shape[-1], bs, 2) \
            if mc.num_kv_layers else 0
        shape = ragged_shapes(toks, seqs, atom,
                              mc.state_chunk_size if kv.state else 0)[-1]
        a, p = shape.atoms, shape.pieces
        tiles = [on_chip((a, atom)), on_chip((a,)), on_chip((a,)),
                 on_chip((a, bps)), on_chip((toks,)), on_chip((seqs,)),
                 on_chip((seqs,))] if atom else [None] * 7
        args = [on_chip((toks,)), on_chip((toks,)), on_chip((toks,)),
                on_chip((seqs, bps)), on_chip((seqs,)), *tiles, sampled,
                on_chip((toks,))]
        state = [SsmBatch(*(on_chip((seqs,)),) * 3, *(on_chip((p,)),) * 3,
                          on_chip((p,), jnp.bool_), on_chip(()))] \
            if kv.state else []
        window = [on_chip((seqs, bps)), on_chip((a, bps))] if windowed else []
    behind = state + window if state or not window else [None] + window
    with mock.patch.object(gg, "default_impl", lambda: "pallas"), \
            mock.patch.object(M, "_state_step_fn", lambda kind: M.select_impl(
                kind, "pallas", {"backend": "tpu"}).fn), \
            mock.patch.object(topo_mod, "_WORLD_TOPOLOGY", None):
        compiled = fn.lower(params, kv, *args, *behind).compile()
    return compiled, kv, params


# ------------------------------------------------------------------- flash
# training attention: mistral-7b (32/8 heads, d 128, S 4096, window 4096),
# phi-2's head_dim 80 (lane-padded to 128 inside the kernel wrapper), and the
# two training cells' own shape (mistral-7b widths, 4 x 2,048 tokens a chip)
FLASH_CASES = {
    "mistral-7b": dict(preset="mistral-7b", batch=1, seq=4096, window=4096),
    "phi-2": dict(preset="phi-2", batch=1, seq=2048, window=None),
    "train-cells": dict(preset="mistral-7b", batch=4, seq=2048, window=4096),
}


def _flash_fn(window, grad):
    f = functools.partial(flash_attention, causal=True, window=window,
                          interpret=False)
    if not grad:
        return f
    return jax.grad(lambda q, k, v: f(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


@_once
def _flash_compiled(one_chip, name, grad):
    case = FLASH_CASES[name]
    h, kvh, d = _widths(case["preset"])
    q = ((case["batch"], case["seq"], h, d), jnp.bfloat16)
    kv = ((case["batch"], case["seq"], kvh, d), jnp.bfloat16)
    return _compile(_flash_fn(case["window"], grad), one_chip, q, kv, kv)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("name", sorted(FLASH_CASES))
def test_flash_compiles(one_chip, name, grad):
    compiled = _flash_compiled(one_chip, name, grad)
    assert "tpu_custom_call" in compiled.as_text()


def test_the_cells_step_holds_three_flash_calls_the_benchmark_can_tell_apart(
        one_chip):
    """``benchmark/metrics/flash_roofline.py`` counts EVERY custom call of
    the train step as one of the three flash kernels and tells them apart by
    result type alone (forward: two arrays of different shapes; dq: one; dkv:
    two of one shape). So a layer's attention is exactly three calls, ``lse``
    never has ``o``'s shape and dK / dV leave their kernel as one shape. The
    blocks are the rule's own, inside the VMEM each kernel asks for (which
    the compile above has held them to)."""
    from benchmark.metrics.flash_roofline import kernel_of
    from deepspeedsyclsupport_tpu.ops.flash_attention import (
        _VMEM_CAP, _default_blocks, _vmem_limit)

    text = _flash_compiled(one_chip, "train-cells", True).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kernel_of(c) for c in calls) == ["dkv", "dq", "fwd"]
    sq_p, skv_p, block = _default_blocks(2048, 2048)
    assert (sq_p, skv_p) == (2048, 2048)
    assert _vmem_limit(block, 128, 2) <= _VMEM_CAP


# ------------------------------------------------------------------- paged
# what the kernels are handed: one layer's [slots, KVH, D] cache (the unit
# tests, chip_smoke.py) or, as in the serving forwards, the whole pool
# [L, slots, KVH, D] and a traced layer index their DMAs take
CACHE_FORMS = ["layer_cache", "whole_pool"]


def _paged_shapes(name, form="layer_cache"):
    """Kernel operands at the engine's DEFAULT geometry for ``name``: the
    pool's head dim is lane-padded to 128 on TPU (phi-2: 80 -> 128). The
    whole pool has the model's full depth and, for phi-2, the benchmark
    cell's 150 blocks (32 layers x 9600 slots x 32 heads x 128)."""
    cfg = RaggedInferenceConfig(num_blocks=150 if name == "phi-2" else None)
    h, kvh, d = _widths(name)
    d = -(-d // 128) * 128
    cache = (cfg.num_blocks * cfg.block_size, kvh, d)
    if form == "whole_pool":
        cache = (get_config(name).num_layers,) + cache
    return cfg, h, d, (cache, jnp.bfloat16)


def _with_layer(kernel, form, **static):
    """``kernel`` with its statics bound; for the whole pool the layer is
    the LAST positional operand, a traced int32 scalar."""
    f = functools.partial(kernel, **static)
    if form == "layer_cache":
        return f, ()
    return (lambda *args: f(*args[:-1], layer=args[-1])), (((), jnp.int32),)


@_once
def _paged_decode(one_chip, name, form="layer_cache"):
    cfg, h, d, cache = _paged_shapes(name, form)
    s = cfg.max_sequences
    f, layer = _with_layer(paged_decode_attention_pallas, form,
                           block_size=cfg.block_size,
                           window=get_config(name).sliding_window)
    return _compile(f, one_chip, ((s, h, d), jnp.bfloat16), cache, cache,
                    ((s, cfg.blocks_per_seq), jnp.int32), ((s,), jnp.int32),
                    *layer)


@pytest.mark.parametrize("form", CACHE_FORMS)
@pytest.mark.parametrize("name", ["mistral-7b", "phi-2"])
def test_paged_decode_compiles(one_chip, name, form):
    _paged_decode(one_chip, name, form)


@_once
def _ragged_default_atom(one_chip, name, form="layer_cache"):
    cfg, h, d, cache = _paged_shapes(name, form)
    bq = cfg.atom_q_size   # the DEFAULT atom: no user-picked atom_q_size
    atoms = cfg.max_tokens_per_batch // bq + cfg.max_sequences
    f, layer = _with_layer(ragged_prefill_attention_pallas, form,
                           block_size=cfg.block_size,
                           window=get_config(name).sliding_window)
    return _compile(f, one_chip, ((atoms, bq, h, d), jnp.bfloat16), cache,
                    cache, ((atoms, cfg.blocks_per_seq), jnp.int32),
                    ((atoms,), jnp.int32), ((atoms,), jnp.int32), *layer)


@pytest.mark.parametrize("form", CACHE_FORMS)
@pytest.mark.parametrize("name", ["mistral-7b", "phi-2"])
def test_ragged_prefill_compiles_with_default_atom(one_chip, name, form):
    """Refused before this file existed: a 128-row atom at 32 heads x d 128
    needs 21-22 MiB of VMEM against Mosaic's 16 MiB default scoped limit."""
    _ragged_default_atom(one_chip, name, form)


# the latent pools of the two cells that have one: [layers, blocks x 64, 640]
# bf16 rows, the value their leading 512 lanes, ONE kv head under the query
# heads of 640 (absorbed). xing4-docs-sat: 32 heads, 128-row atoms;
# dsv2-answers-sat: 128 heads, 16-row atoms, 64 sequences
LATENT = {
    "xing4": dict(layers=6, slots=6400 * 64, row=640, v_dim=512, heads=32,
                  block_size=64, max_context=16384, max_sequences=16,
                  max_tokens=768, atom=128, head_tile=16),
    "dsv2": dict(layers=5, slots=7680 * 64, row=640, v_dim=512, heads=128,
                 block_size=64, max_context=8192, max_sequences=64,
                 max_tokens=768, atom=16, head_tile=128)}
# KV blocks a loop step of each entry takes at those tiles (the rule's own
# choice: _kv_pages_per_step)
LATENT_PAGES = {"ragged_prefill": 4, "paged_decode": 8}


@pytest.mark.parametrize("kernel", ["ragged_prefill", "paged_decode"])
@pytest.mark.parametrize("cell", sorted(LATENT))
def test_latent_kernels_compile_at_the_cells_widths(one_chip, kernel, cell):
    """Refused while the pool had a head axis of one (tiled up to two in
    HBM: "Slice shape along dimension 2 must be aligned to tiling (2)"); the
    128-row atom of 32 x 640 runs as two head tiles of 16, the 16-row atom
    of 128 x 640 as one of 128 (the same tile), and a 128-row atom of 128 x
    640 would run as eight of 16. Each compiles with the loop step the rule
    picks for its tile, four KV blocks under the atoms and eight under one
    row, inside the VMEM it states."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        _VMEM_CAP, _head_tile, _kv_pages_per_step, _ragged_vmem_limit,
        default_atom_rows)

    g = LATENT[cell]
    rows = g["atom"] if kernel == "ragged_prefill" else 1
    tile = (rows, _head_tile(rows, g["heads"], 1, g["row"], g["block_size"],
                             2), 1, g["row"], g["block_size"], 2)
    pages = _kv_pages_per_step(*tile, True)
    assert pages == LATENT_PAGES[kernel]
    assert _ragged_vmem_limit(*tile, pages) <= _VMEM_CAP
    # the atom is the engine's own choice in both cells
    assert default_atom_rows(128, g["heads"], 1, g["row"], g["block_size"],
                             2) == g["atom"]
    assert _head_tile(g["atom"], g["heads"], 1, g["row"], g["block_size"],
                      2) == g["head_tile"]
    assert _head_tile(128, 128, 1, 640, 64, 2) == 16
    pool = ((g["layers"], g["slots"], g["row"]), jnp.bfloat16)
    bps = g["max_context"] // g["block_size"]
    if kernel == "ragged_prefill":
        n = g["max_tokens"] // g["atom"] + g["max_sequences"] + 1
        q = ((n, g["atom"], g["heads"], g["row"]), jnp.bfloat16)
        extra = (((n,), jnp.int32), ((n,), jnp.int32))
        f = ragged_prefill_attention_pallas
    else:
        n = g["max_sequences"]
        q = ((n, g["heads"], g["row"]), jnp.bfloat16)
        extra = (((n,), jnp.int32),)
        f = paged_decode_attention_pallas
    compiled = _compile(
        lambda q, k, tables, *rest: f(
            q, k, None, tables, *rest[:-1], block_size=g["block_size"],
            layer=rest[-1], v_dim=g["v_dim"]),
        one_chip, q, pool, ((n, bps), jnp.int32), *extra, ((), jnp.int32))
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and kernel in calls[0].split(" = ")[0]


# a K-and-V pool under an indexer's selection, at keye-video-sat's tile: 128
# rows x 32 heads over 4 kv heads, d 128, blocks of 64, a 49,152-token table
KEYE_STEP_KEYS = 512     # eight blocks of 64: four lane tiles of the selection
KEYE_TILE = dict(rows=128, heads=32, kv_heads=4, d=128, block_size=64,
                 max_context=49152, atoms=15, blocks=6272)


def test_the_selected_k_and_v_tile_compiles_at_the_rules_width(one_chip):
    """The ragged kernel under a selection (``dsa_prefill``) at the cell's
    tile, with the loop step the rule picks for it (whole 128-key lane
    tiles of the selection, one kv head's scores filling the budget a
    step's scores have, K and V turned head-major in the pool's dtype),
    inside the VMEM it states."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        _STEP_SCORE_BYTES, _VMEM_CAP, _head_tile, _kv_pages_per_step,
        _ragged_vmem_limit, _selection_pages, kv_step_keys)

    g = KEYE_TILE
    shape = (g["heads"], g["kv_heads"], g["d"], g["block_size"], 2)
    assert _head_tile(g["rows"], *shape) == g["heads"]
    pages = _selection_pages(
        _kv_pages_per_step(g["rows"], *shape, False), g["block_size"])
    keys = pages * g["block_size"]
    assert keys == KEYE_STEP_KEYS and keys % 128 == 0
    assert kv_step_keys(g["rows"], *shape, False, True) == keys
    # ONE kv head's scores fill the budget a step's scores have
    rows = g["rows"] * g["heads"] // g["kv_heads"]
    assert rows * keys * 4 <= _STEP_SCORE_BYTES
    assert _ragged_vmem_limit(g["rows"], *shape, pages, True) <= _VMEM_CAP
    bps = g["max_context"] // g["block_size"]
    a = g["atoms"]
    pool = ((2, g["blocks"] * g["block_size"], g["kv_heads"], g["d"]),
            jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, tables, pos0, qlen, sel, layer:
        ragged_prefill_attention_pallas(
            q, k, v, tables, pos0, qlen, block_size=g["block_size"],
            layer=layer, sel=sel),
        one_chip, ((a, g["rows"], g["heads"], g["d"]), jnp.bfloat16), pool,
        pool, ((a, bps), jnp.int32), ((a,), jnp.int32), ((a,), jnp.int32),
        ((a, g["rows"], g["max_context"]), jnp.int8), ((), jnp.int32))
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "dsa_prefill" in calls[0].split(" = ")[0]


# blocks chosen a KV head, at sala-docs-sat's tile: 32 heads over 2 kv heads,
# 128-row atoms, tables of 1,548 blocks of 64 (99,072 keys), 15 atoms a call
SALA_TILE = dict(rows=128, heads=32, kv_heads=2, d=128, block_size=64,
                 table=1548, atoms=15, blocks=12544)


def test_the_block_selection_compiles_and_no_mask_of_keys_is_made(one_chip):
    """The ragged kernel under a selection of BLOCKS a kv head
    (``bsa_prefill``: ``sel [A, KVH, blocks, BQ]``) at the cell's tile: four
    blocks a step (256 keys, 387 whole steps of the table), the operand cut
    into steps ``[15, 387, 2, 4, 128]`` int8 (5.9 MB) and a step's ``[2, 4,
    128]`` widened to its keys in VMEM (a product with a one-hot contracted
    over the step's four blocks: the chip's compiler takes the transposed
    left side), inside the VMEM it states. And the guard that the mechanism
    ENGAGED where it is served: the compiled ``ragged_forward`` of
    ``minicpm-sala`` at the cell's shapes holds no int8 array larger than
    the block selection as its kernel writes it (PRs 59-67 held ``s8[15, 2,
    128, 99072]``, 380 MB a sparse layer, written, turned and read)."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        _VMEM_CAP, _head_tile, _kv_pages_per_step, _ragged_vmem_limit,
        _selection_pages, kv_step_keys)

    g = SALA_TILE
    shape = (g["heads"], g["kv_heads"], g["d"], g["block_size"], 2)
    assert _head_tile(g["rows"], *shape) == g["heads"]
    pages = _selection_pages(
        _kv_pages_per_step(g["rows"], *shape, False), g["block_size"])
    assert pages == 4 and g["table"] % pages == 0
    assert kv_step_keys(g["rows"], *shape, False, True) == 256
    assert _ragged_vmem_limit(g["rows"], *shape, pages, True,
                              g["kv_heads"]) <= _VMEM_CAP
    a = g["atoms"]
    pool = ((3, g["blocks"] * g["block_size"], g["kv_heads"], g["d"]),
            jnp.bfloat16)
    chosen = (a, g["kv_heads"], g["table"], g["rows"])
    compiled = _compile(
        lambda q, k, v, tables, pos0, qlen, sel, layer:
        ragged_prefill_attention_pallas(
            q, k, v, tables, pos0, qlen, block_size=g["block_size"],
            layer=layer, sel=sel, name="bsa_prefill"),
        one_chip, ((a, g["rows"], g["heads"], g["d"]), jnp.bfloat16), pool,
        pool, ((a, g["table"]), jnp.int32), ((a,), jnp.int32),
        ((a,), jnp.int32), (chosen, jnp.int8), ((), jnp.int32))
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "bsa_prefill" in calls[0].split(" = ")[0]
    steps = g["table"] // pages
    assert f"s8[{a},{steps},{g['kv_heads']},{pages},{g['rows']}]" in text

    def widest_int8(text):
        return max(int(np.prod([int(n) for n in dims.split(",")]))
                   for dims in re.findall(r"\bs8\[([0-9,]+)\]", text))

    assert widest_int8(text) == int(np.prod(chosen))
    forward, _kv, _params = _cell_forward(one_chip, "minicpm-sala-d12",
                                          "ragged_forward")
    # the widest is the selection as ``bsa_select`` writes it, its blocks
    # padded to whole lane tiles: s8[30, 128, 1664]
    lanes = -(-g["table"] // 128) * 128
    assert widest_int8(forward.as_text()) \
        == a * g["kv_heads"] * g["rows"] * lanes


# a LATENT pool under an indexer's selection, at glm5-docs-sat's tile: 64
# heads x 640 over one row a token, blocks of 64, a 24,576-token table
GLM5_TILE = dict(rows=32, heads=64, d=640, v_dim=512, block_size=64,
                 max_context=24576, atoms=16 + 768 // 32 + 1, blocks=6272)


def test_the_selected_latent_tile_compiles_at_the_rules_width(one_chip):
    """The ragged kernel's LATENT tile under a selection (``dsa_prefill``
    over a pool with no V: PR 65) at the cell's tile: the engine's own atom
    (32 rows, all 64 heads in one grid step), the loop step the rule picks
    (four blocks = 256 keys, whole 128-key lane tiles of the selection),
    inside the VMEM it states."""
    from deepspeedsyclsupport_tpu.ops.paged_attention import (
        _VMEM_CAP, _head_tile, _kv_pages_per_step, _ragged_vmem_limit,
        _selection_pages, default_atom_rows, kv_step_keys)

    g = GLM5_TILE
    shape = (g["heads"], 1, g["d"], g["block_size"], 2)
    assert default_atom_rows(128, *shape) == g["rows"]
    assert _head_tile(g["rows"], *shape) == g["heads"]
    pages = _selection_pages(
        _kv_pages_per_step(g["rows"], *shape, True), g["block_size"])
    assert pages == 4 and pages * g["block_size"] % 128 == 0
    assert kv_step_keys(g["rows"], *shape, True, True) == 256
    assert _ragged_vmem_limit(g["rows"], *shape, pages) <= _VMEM_CAP
    bps = g["max_context"] // g["block_size"]
    a = g["atoms"]
    compiled = _compile(
        lambda q, k, tables, pos0, qlen, sel, layer:
        ragged_prefill_attention_pallas(
            q, k, None, tables, pos0, qlen, block_size=g["block_size"],
            layer=layer, sel=sel, v_dim=g["v_dim"]),
        one_chip, ((a, g["rows"], g["heads"], g["d"]), jnp.bfloat16),
        ((5, g["blocks"] * g["block_size"], g["d"]), jnp.bfloat16),
        ((a, bps), jnp.int32), ((a,), jnp.int32), ((a,), jnp.int32),
        ((a, g["rows"], g["max_context"]), jnp.int8), ((), jnp.int32))
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "dsa_prefill" in calls[0].split(" = ")[0]


def test_the_latent_indexer_forward_compiles_and_fits(one_chip):
    """``ragged_forward`` of ``glm-5`` WHOLE at the cell's widths and shapes
    (five layers; 16 sequences, 768 rows, contexts to 24,576, the whole
    pool of 6,272 blocks): the selection's three kernels are custom calls
    under their own names over a pool of TWO arrays (the latent rows and
    the indexer's keys, both aliased to the result, neither copied), the
    scopes reach the compiled text with latent attention's beside them, and
    weights, pool and temporaries fit the chip's 15.75 GiB with room for the
    plain reference beside them."""
    from benchmark import scopes

    compiled, kv, _params = _cell_forward(one_chip, "glm-5-ep16-d5",
                                          "ragged_forward")
    layers, slots = 5, 6272 * 64
    assert kv.v is None and kv.k.shape == (layers, slots, 640)
    assert kv.idx.shape == (layers, slots // 2, 256)
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert {"dsa_index_scores", "dsa_select", "dsa_prefill"} <= calls
    assert "ragged_prefill" not in calls and "paged_decode" not in calls
    under = scopes.instructions_under(
        text, ("dsa_index", "dsa_select", "dsa_attend", "dsa_rows",
               "mla_proj", "mla_absorb", "moe_experts"))
    assert set(under.values()) >= {"dsa_index", "dsa_select", "dsa_rows",
                                   "mla_proj", "mla_absorb", "moe_experts"}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= (kv.k.size + kv.idx.size) * 2
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and (f"bf16[{layers},{slots}," in ln
                     or f"bf16[{layers},{slots // 2}," in ln)]
    gib = 2**30
    assert m.argument_size_in_bytes / gib == pytest.approx(13.26, abs=0.05)
    assert m.temp_size_in_bytes < 0.75 * gib
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) / gib < 14.0


def test_the_engine_counts_a_selected_atoms_steps_as_the_wrapper_walks_them(
        monkeypatch):
    """``InferenceEngineV2._kv_step_keys`` (what ``ragged.attention_work``
    rounds a tile's context up to) for a tiny model with an indexer: the
    atoms' steps are the rule's for their tile made whole lane tiles of the
    selection, the very choice the kernel's wrapper makes (it asks the same
    two functions), and the one-row tile's stays one block."""
    from deepspeedsyclsupport_tpu.inference.v2 import InferenceEngineV2
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    model = build_model(
        "keye-vl2-30b-a3b", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, vocab_size=256, num_experts=8, num_experts_per_tok=3,
        num_experts_held=4, index_topk=8, index_heads=2, index_head_dim=8,
        max_seq_len=128, dtype="float32")
    seen = []
    call = pa._tiled_call
    monkeypatch.setattr(pa, "_tiled_call", lambda *a, **kw: (
        seen.append((a[4].shape[1], kw["pages"])), call(*a, **kw))[1])
    eng = InferenceEngineV2(
        model, model.init_params(), dtype=jnp.float32, block_size=4,
        max_context=64, max_tokens_per_batch=16, max_sequences=4,
        num_blocks=48, prefill_attn="kernel_interpret",
        decode_attn="pallas_interpret", atom_q_size=8)
    tile = (4, 2, eng.kv.k.shape[-1], 4, 4)
    pages = pa._selection_pages(pa._kv_pages_per_step(8, *tile, False), 4)
    assert eng._kv_step_keys == (4 * pages, 4)
    assert pa.kv_step_keys(8, *tile, False, True) == 4 * pages
    eng.put([0], [list(range(1, 12))])
    # the selected atoms' call (8 rows); the one-token rows go by a gather
    assert (8, pages) in seen and all(p == pages for r, p in seen if r == 8)


# ------------------------------------------------- the expert weights' stack
def test_decode_forward_reads_the_expert_stack_in_place(one_chip):
    """``decode_forward`` at OLMoE's widths, two layers, as the engine builds
    it: the grouped GEMMs (``ragged-dot``: a custom call, which takes whole
    buffers) read the stacked ``[L, 64, ., .]`` leaves where they lie. While
    the leaves rode as the layer scan's xs, each layer's three matrices were
    copied out first (``dynamic-slice_bitcast_fusion``: 805 MB of
    temporaries, 1.56 x the GEMMs' time on the chip)."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (BlockedKV,
                                                                MoeCounters)
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("olmoe-1b-7b", num_layers=2, dtype="bfloat16")
    cfg = model.config
    bs, slots, seqs, bps = 64, 64 * 64, 32, 64

    def on_chip(tree, floats=None):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, floats if floats is not None and jnp.issubdtype(
                x.dtype, jnp.floating) else x.dtype, sharding=one_chip), tree)

    params = _serving_params(model, one_chip)
    pool = jnp.zeros((2, slots, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    kv = on_chip(BlockedKV(pool, pool, MoeCounters(
        jnp.zeros((2, cfg.num_experts), jnp.int32), jnp.int32(0))))
    i32 = on_chip(jnp.zeros((seqs,), jnp.int32))
    compiled = M.build_decode_forward_fn(model, bs, "pallas").lower(
        params, kv, i32, i32, on_chip(jnp.zeros((seqs, bps), jnp.int32)),
        on_chip(jnp.zeros((seqs,), jnp.bool_))).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 3      # the three GEMMs are there
    e, d, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    slices = [ln.split(" = ")[0].strip() for ln in text.splitlines()
              if f" = bf16[{e},{d},{f}]" in ln or f" = bf16[{e},{f},{d}]" in ln]
    assert not slices, f"one layer's expert matrix is materialised: {slices}"
    one_matrix = e * d * f * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix


# ------------------------------- the grouped GEMM whose row tile fits the expert
# (experts held, d, f, the program's rows, experts routed over): the two
# serving forwards of the three sparse cells
GROUPED = {
    "olmoe_decode": (64, 2048, 1024, 32 * 8, 64),
    "olmoe_chunk": (64, 2048, 1024, 768 * 8, 64),
    "xing4_decode": (64, 3584, 1024, 16 * 4, 64),
    "xing4_chunk": (64, 3584, 1024, 768 * 4, 64),
    "dsv2_decode": (40, 5120, 1536, 64 * 6, 160),
    "dsv2_chunk": (40, 5120, 1536, 768 * 6, 160),
}


@pytest.mark.parametrize("shape", GROUPED.values(), ids=GROUPED.keys())
def test_the_grouped_gemm_compiles_at_the_cells_widths(one_chip, shape):
    """Both kernels of an expert layer, the tile and the weight blocks the
    shape picks, a two-layer stack read at a traced layer: what the chip's
    compiler refuses (VMEM, a block off the tiling) it refuses here."""
    from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg

    held, d, f, rows, experts = shape
    tile = gg.row_tile(rows, experts)

    def layer(x, w_gate, w_up, w_down, sizes, group, l):
        tiles = gg.tile_rows(sizes, group, tile)
        mid = gg.grouped_glu(x[tiles.src], w_gate, w_up, tiles, layer=l,
                             act=jax.nn.silu)
        return gg.grouped_matmul(mid, w_down, tiles, layer=l)[tiles.dest]

    bf16 = jnp.bfloat16
    compiled = _compile(
        layer, one_chip, ((rows, d), bf16), ((2, held, d, f), bf16),
        ((2, held, d, f), bf16), ((2, held, f, d), bf16),
        ((held,), jnp.int32), ((rows,), jnp.int32), ((), jnp.int32))
    calls = [ln.split(" = ")[0].strip().lstrip("%")
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(c.split(".")[0] for c in calls) == ["grouped_glu",
                                                      "grouped_matmul"]
    # no layer's matrix is copied out of the stack for them
    assert compiled.memory_analysis().temp_size_in_bytes < held * d * f * 2


def test_decode_forward_on_the_tpu_takes_the_kernel(one_chip, monkeypatch):
    """``decode_forward`` at OLMoE's widths as the TPU traces it (the path
    is the platform's; here the test stands in for it): no ``ragged-dot``
    left, the two kernels under the ``moe_experts`` scope by their OWN
    instruction names, where ``benchmark/scopes.py`` finds what
    ``moe_roofline`` and ``moe_share_pct`` sum, and the stack read in
    place."""
    from benchmark import scopes
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (BlockedKV,
                                                                MoeCounters)
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg

    monkeypatch.setattr(gg, "default_impl", lambda: "pallas")
    model = build_model("olmoe-1b-7b", num_layers=2, dtype="bfloat16")
    cfg = model.config
    bs, slots, seqs, bps = 64, 64 * 64, 32, 64

    def on_chip(tree, floats=None):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, floats if floats is not None and jnp.issubdtype(
                x.dtype, jnp.floating) else x.dtype, sharding=one_chip), tree)

    params = _serving_params(model, one_chip)
    pool = jnp.zeros((2, slots, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    zero = jnp.int32(0)
    kv = on_chip(BlockedKV(pool, pool, MoeCounters(
        jnp.zeros((2, cfg.num_experts), jnp.int32), zero, None, zero)))
    i32 = on_chip(jnp.zeros((seqs,), jnp.int32))
    compiled = M.build_decode_forward_fn(model, bs, "pallas").lower(
        params, kv, i32, i32, on_chip(jnp.zeros((seqs, bps), jnp.int32)),
        on_chip(jnp.zeros((seqs,), jnp.bool_))).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    under = scopes.instructions_under(text, ("moe_experts",))
    assert {"grouped_glu", "grouped_matmul"} \
        <= {name.split(".")[0] for name in under}
    assert not any(name.startswith(prefix) for name in under
                   for prefix, _label in scopes.RAGGED_DOT_KERNELS)
    e, d, f = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    assert not [ln for ln in text.splitlines()
                if f" = bf16[{e},{d},{f}]" in ln
                or f" = bf16[{e},{f},{d}]" in ln]
    assert compiled.memory_analysis().temp_size_in_bytes < e * d * f * 2


# ------------------------------------ the experts' combine scatters nothing
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_experts_combine_scatters_nothing(one_chip, program, monkeypatch):
    """Both serving forwards of ``deepseek-v2`` at ``dsv2-answers-sat``'s
    widths and shapes (the dense layer and ONE expert layer of the five, 40
    of 160 experts held; 64 sequences, 768 rows, the latent pool): the
    scopes of the expert layer reach the compiled text, and under
    ``moe_combine`` there is no ``scatter(``: a token's k rows are gathered
    out of the tile layout and summed (the scatter-add of PRs 26-57 ran a
    row at a time, ~26 GB/s: 1.8-2.0 ms a layer of a mixed round here)."""
    from benchmark import scopes
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (BlockedKV,
                                                                MoeCounters)
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import grouped_gemm as gg

    monkeypatch.setattr(gg, "default_impl", lambda: "pallas")
    g = LATENT["dsv2"]
    model = build_model("deepseek-v2", num_layers=2, num_experts_held=40,
                        vocab_size=25600, dtype="bfloat16")
    cfg = model.config
    bs, seqs, toks, atom = (g["block_size"], g["max_sequences"],
                            g["max_tokens"], g["atom"])
    bps = g["max_context"] // bs

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _serving_params(model, one_chip)
    zero = on_chip(())
    kv = BlockedKV(on_chip((2, g["slots"], g["row"]), jnp.bfloat16), None,
                   MoeCounters(on_chip((1, cfg.num_experts)), zero, zero,
                               zero))
    sampled = on_chip((seqs + 3,))
    if program == "decode_forward":
        fn = M.build_decode_forward_fn(model, bs, "pallas")
        args = (on_chip((seqs,)), on_chip((seqs,)), on_chip((seqs, bps)),
                on_chip((seqs,), jnp.bool_), sampled, on_chip((seqs,)))
    else:
        fn = M.build_ragged_forward_fn(model, bs, "kernel")
        atoms = seqs + toks // atom + 1
        args = (on_chip((toks,)), on_chip((toks,)), on_chip((toks,)),
                on_chip((seqs, bps)), on_chip((seqs,)),
                on_chip((atoms, atom)), on_chip((atoms,)), on_chip((atoms,)),
                on_chip((atoms, bps)), on_chip((toks,)), on_chip((seqs,)),
                on_chip((seqs,)), sampled, on_chip((toks,)))
    text = fn.lower(params, kv, *args).compile().as_text()
    under = scopes.instructions_under(
        text, ("moe_route", "moe_experts", "moe_combine", "moe_shared"))
    assert set(under.values()) == {"moe_route", "moe_experts", "moe_combine",
                                   "moe_shared"}
    assert {"grouped_glu", "grouped_matmul"} \
        <= {name.split(".")[0] for name in under}
    # (fused instructions carry their own op_name: every line is looked at)
    combine = [ln for ln in text.splitlines()
               if "op_name=" in ln and "/moe_combine/" in ln]
    assert combine and not [ln for ln in combine
                            if re.search(r" scatter\(", ln)]


# ------------------------------------- the forwards' tokens from the device
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_forwards_take_decode_tokens_from_the_sampler(one_chip, program):
    """Both serving forwards at phi-2's widths (two layers) and the cell's
    shapes, as the engine builds and calls them since a round launches the
    next forward before it reads the sampled tokens back: ``sampled`` (the
    sampler's ``[max_sequences]`` output, here with a tail of one behind it)
    and ``take_from`` follow the other operands, and the select is part of
    the forward: ONE program for the chip, the kernels still custom calls,
    the sampler's output a live parameter of it."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import BlockedKV
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("phi-2", num_layers=2, dtype="bfloat16")
    cfg = model.config
    bs, blocks, seqs, toks, atom = 64, 150, 32, 768, 128
    bps = cfg.max_seq_len // bs

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _serving_params(model, one_chip)
    pool = on_chip((2, blocks * bs, cfg.num_kv_heads, 128), jnp.bfloat16)
    kv = BlockedKV(pool, pool)
    sampled = on_chip((seqs + 1,))
    if program == "decode_forward":
        fn = M.build_decode_forward_fn(model, bs, "pallas")
        args = (on_chip((seqs,)), on_chip((seqs,)), on_chip((seqs, bps)),
                on_chip((seqs,), jnp.bool_), sampled, on_chip((seqs,)))
    else:
        fn = M.build_ragged_forward_fn(model, bs, "kernel")
        atoms = seqs + toks // atom + 1
        args = (on_chip((toks,)), on_chip((toks,)), on_chip((toks,)),
                on_chip((seqs, bps)), on_chip((seqs,)),
                on_chip((atoms, atom)), on_chip((atoms,)), on_chip((atoms,)),
                on_chip((atoms, bps)), on_chip((toks,)), on_chip((seqs,)),
                on_chip((seqs,)), sampled, on_chip((toks,)))
    text = fn.lower(params, kv, *args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"s32[{seqs + 1}]" in text, "the sampler's output is not read"


def test_paged_kernel_is_a_tpu_custom_call(one_chip):
    """The compiled text names the Mosaic kernel — the same string
    ``chip_smoke.py`` looks for in the programs it ran on the chip."""
    assert "tpu_custom_call" in _paged_decode(one_chip, "phi-2").as_text()


def test_the_two_kernels_carry_their_names_onto_the_custom_call(one_chip):
    """What a device trace prints for the instruction (it read
    ``closed_call.<n>`` for both kernels): the decode entry's calls are
    ``paged_decode``, the prefill entry's ``ragged_prefill``."""
    for compiled, name in (
            (_paged_decode(one_chip, "phi-2", "whole_pool"), "paged_decode"),
            (_ragged_default_atom(one_chip, "phi-2", "whole_pool"),
             "ragged_prefill")):
        calls = [ln for ln in compiled.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert calls and all(
            ln.split(" = ")[0].split("%")[-1].split(".")[0] == name
            for ln in calls), calls


# ------------------------------------- no forward copies a weight it is handed
# the benchmark's configurations whose forwards project q, k and v through
# ``model._qkv`` or a lightning layer's three products, at the floor of 8 MiB
# PR 64 held them to; and the three whose latent attention projects through
# its ranks (``model._mla_rows`` / ``_mla_out``; GLM-5's indexer beside it),
# at 1 MiB, so that the parts of ``w_kvb`` count (Xing4's are 4 MiB)
WEIGHT_COPY_CONFIGS = {
    **dict.fromkeys((
        "ouro-2.6b", "command-a-plus-ep8-d4", "brumby-14b-d8",
        "falcon-h1-34b-d6", "minicpm-sala-d12", "phi-2",
        "nemotron3-nano-ep4-d26", "solar-open2-ep8-d4", "olmoe-1b-7b-d10",
        "keye-vl2-30b-a3b-ep8-d12"), 8 << 20),
    # (GLM-5's two programs are the indexer's cases' too: compiled once)
    "glm-5-ep16-d5": 1 << 20, "deepseek-v2-ep4-d5": 1 << 20,
    "xing4-29b-a4b-d6": 1 << 20}
_COPY = re.compile(r"%?([\w.\-]+) = (bf16|f32)\[([\d,]+)\]\S* copy\(")


def _merges(fine, coarse):
    """Whether the dims ``fine`` merge into the dims ``coarse``: each of
    ``coarse`` one of ``fine`` or the product of two (heads x a head's
    width), each of ``fine`` used once."""
    if not coarse:
        return not fine
    want, rest = coarse[0], coarse[1:]
    for i, a in enumerate(fine):
        left = fine[:i] + fine[i + 1:]
        if a == want and _merges(left, rest):
            return True
        if want % a == 0 and any(
                a * b == want and _merges(left[:j] + left[j + 1:], rest)
                for j, b in enumerate(left)):
            return True
    return False


def _weight_copies(text, params, floor=8 << 20):
    """The ``copy`` instructions of a compiled ``text`` that write ``floor``
    bytes or more in the shape of a weight: a leaf of ``params`` whole or
    one layer's slice of a stacked leaf, its dims in any order (the bitcast
    before a copy may have turned them), one or more of them regrouped in
    two (``[h x d, c]`` copied as ``[h, d, c]``) and without the 1s. Not
    the copy behind an ``optimization_barrier``: that one moves a product's
    RESULT (rows x out, which in Xing4's mixed forward, 768 rows over a
    query latent of 768, has the dims of the weight)."""
    def dims(shape):
        return tuple(sorted(d for d in shape if d > 1))

    weights = {dims(shape) for leaf in jax.tree_util.tree_leaves(params)
               for shape in (leaf.shape, leaf.shape[1:])}
    found = []
    for line in text.splitlines():
        m = _COPY.search(line)
        if m is None or "optimization_barrier" in line:
            continue
        name, dtype, shape = m.groups()
        shape = dims(int(d) for d in shape.split(","))
        nbytes = (2 if dtype == "bf16" else 4) * functools.reduce(
            lambda a, b: a * b, shape, 1)
        if nbytes >= floor and any(_merges(shape, w) for w in weights):
            found.append(f"{name} {dtype}{list(shape)}")
    return found


def test_a_regrouped_copy_is_told_from_an_activation():
    """``_weight_copies`` on hand-made lines: DeepSeek-V2's ``w_qb``
    regrouped by head and turned is a weight's copy, at any floor under its
    bytes; the attended latents of 768 rows are not, nor is a product's
    result behind a barrier."""
    params = {"w_qb": jax.ShapeDtypeStruct((5, 24576, 1536), jnp.bfloat16),
              "w_uk": jax.ShapeDtypeStruct((5, 128, 512, 128), jnp.bfloat16)}
    text = "\n".join([
        "%copy.1 = bf16[128,192,1536]{2,0,1:T(8,128)(2,1)} copy(%p.1)",
        "%copy.2 = bf16[768,128,512]{2,0,1:T(8,128)(2,1)} copy(%p.2)",
        "%copy.3 = bf16[1,128,64,1024]{3,1,2,0} copy(%p.3)",
        "%copy.4 = bf16[1536,24576]{0,1} copy(%f.4), metadata={op_name="
        "\"jit(f)/mla_proj/optimization_barrier\"}",
        "%copy.5 = f32[8,16,512,128]{3,2,1,0} copy(%p.5)"])
    assert _weight_copies(text, params, floor=1 << 20) == [
        "copy.1 bf16[128, 192, 1536]", "copy.5 f32[8, 16, 128, 512]"]
    assert _weight_copies(text, params, floor=80 << 20) == []
    assert _merges((2, 3, 4), (6, 4)) and _merges((2, 3, 4), (3, 8))
    assert not _merges((2, 3, 4), (24,)) and not _merges((6, 4), (2, 3, 4))


@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
@pytest.mark.parametrize("config", WEIGHT_COPY_CONFIGS)
def test_the_serving_forwards_copy_no_weight(one_chip, config, program):
    """Both serving forwards of thirteen families WHOLE at their cells'
    shapes, handed the weights as the engine holds them
    (``model.serving_layout``): the compiled text copies no parameter-shaped
    buffer of 8 MiB or more (1 MiB or more in the three latent families).
    Handed the model's public ``[in, out]`` tree the v5e compiler re-laid
    the q, k and v projections' weights inside the program, every forward:
    Ouro's three stacks ``[48, 2048, 2048]`` (1.13 GiB of temporaries, 3 ms
    of 43), SALA's three lightning stacks ``[9, 4096, 4096]``, Command A+'s
    ``wq`` ``[4096, 16384]`` a layer behind a slice of its own, one or three
    slices a layer in Brumby, Falcon-H1, Nemotron-3, Solar-Open2, OLMoE and
    Keye; phi-2 alone copied nothing (PERF.md section 6, PR 64). What stays is the layer's slice into ``S(1)``: the
    compiler's prefetch of the next operand, in the layout it is stored
    in.

    The latent families (PR 66), MiB a layer at the public tree, every
    forward: DeepSeek-V2 (128 heads) ``w_qb`` ``[1536, 24576]`` turned (72)
    and THEN regrouped by head, ``[128, 192, 1536]{2,0,1}`` (72 more), and
    ``w_kvb`` ``[512, 32768]`` turned (32) in both programs, 176 of
    weights; GLM-5 ``w_qb`` ``[2048, 16384]`` 64, ``w_kvb`` ``[512, 28672]``
    28 and the indexer's ``w_qi`` ``[2048, 4096]`` 16; Xing4 ``w_qb`` 9 and
    ``w_kvb`` 8. (The ``f32[128, 512, 64]`` beside them in DeepSeek-V2's
    ``decode_forward`` is not a part of ``w_kvb``: it is the queries in the
    latent, 64 rows x 128 heads x 512, and stays.) ``w_kvb`` is read twice,
    contracted over ``nope`` and over the latent, so ONE leaf in either
    order is still copied in the mixed forward (32 MiB at 128 heads): the
    serving tree holds its two parts. With ``w_qb`` ``[out, in]`` the
    regrouping stays at 128 heads in both programs, and with the rotated
    columns a leaf of their own, in ``decode_forward`` (24 MiB): seen
    through the reshape to heads the compiler puts 128 heads in the lanes;
    ``_mla_rows`` ends the product at a barrier and no form is copied."""
    compiled, _kv, params = _cell_forward(one_chip, config, program)
    assert not _weight_copies(compiled.as_text(), params,
                              WEIGHT_COPY_CONFIGS[config])


# ----------------------------------- a looped stack holds its pool ONCE
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_looped_forwards_hold_the_pool_once(one_chip, program):
    """Both serving forwards of ``ouro-2.6b`` WHOLE (48 layers, four passes,
    the cell's shapes: 16 sequences, 256 rows, 80 blocks of 64): the outer
    loop over passes carries the 7.5 GiB pool [192, 5120, 16, 128] x 2 as
    the layer scan inside it does, in place. A copy of the carry through the
    nested loops would be a second pool, which the chip's 15.75 GiB do not
    hold beside 4.97 GiB of weights: the whole pool is aliased to the
    result and the temporaries are a few MiB (handed ``[in, out]`` weights
    the v5e compiler laid the q, k and v stacks out transposed once a
    forward, 1.13 GiB: ``model.serving_layout``): arguments + temporaries
    less what is aliased are 12.5 GiB. The kernels are the paged custom
    calls and the passes' scopes reach the compiled text
    (``benchmark/scopes.py``)."""
    from benchmark import flops, scopes

    compiled, kv, _params = _cell_forward(one_chip, "ouro-2.6b", program)
    assert kv.k.shape == (192, 80 * 64, 16, 128) and kv.k.size < 2**31
    gib = flops.program_bytes(compiled) / 2**30
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * kv.k.size * 2
    assert m.temp_size_in_bytes < 0.05 * 2**30
    assert 12.4 < gib < 12.6, gib
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    under = scopes.instructions_under(text, ("loop_pass", "loop_exit"))
    assert {"loop_pass", "loop_exit"} == set(under.values())


# ------------------------------------------- sparse attention behind an indexer
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_indexer_forwards_compile_and_copy_no_pool(one_chip, program):
    """Both serving forwards of ``keye-vl2-30b-a3b`` WHOLE at the cell's
    widths and shapes (twelve layers; 8 sequences, 768 rows, contexts to
    49,152, the whole pool of 6,272 blocks a layer): the three kernels of
    the selection path are custom calls under their own names
    (``dsa_index_scores``, ``dsa_select``, the ragged kernel under a mask as
    ``dsa_prefill``; ``decode_forward``, all one-token rows, holds the first
    two), the scopes reach the compiled text, the one-token rows' route
    sorts and scatters nothing under ``dsa_select`` and converts no
    sequence's keys ``[8, 49152, 64]`` to float32 under ``dsa_index`` (the
    route of PR 45 did all three in both programs: 4.4 ms of sorts and 7 ms
    of copies a forward), all three pools
    are aliased to the result, and no pool is copied: the indexer's, two
    slots a row, is written and gathered in the layout it is carried in (a
    64-wide row of its own was stored slot-minor and copied whole every
    layer: 48 ms a forward on the v5e, PERF.md section 6, PR 45)."""
    from benchmark import scopes

    compiled, kv, _params = _cell_forward(
        one_chip, "keye-vl2-30b-a3b-ep8-d12", program)
    layers, slots, seqs, bps, index_dim = 12, 6272 * 64, 8, 768, 64
    assert kv.k.shape[:2] == (layers, slots)
    assert kv.idx.shape == (layers, slots // 2, 2 * index_dim)
    kernels = {"dsa_index_scores", "dsa_select"} | (
        {"dsa_prefill"} if program == "ragged_forward" else set())
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert kernels <= calls and "ragged_prefill" not in calls
    under = scopes.instructions_under(
        text, ("dsa_index", "dsa_select", "dsa_attend", "dsa_rows"))
    assert set(under.values()) >= {"dsa_index", "dsa_select", "dsa_rows"}
    # (fused instructions carry their own op_name: every line is looked at)
    lines = [ln for ln in text.splitlines() if "op_name=" in ln]
    assert not [ln for ln in lines if "/dsa_select/" in ln
                and re.search(r" (sort|scatter)\(", ln)]
    keys = f"f32[{seqs},{bps * 64},{index_dim}]"
    assert not [ln for ln in lines if "/dsa_index/" in ln and keys in ln]
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= (2 * kv.k.size + kv.idx.size) * 2
    assert m.temp_size_in_bytes < 0.5 * 2**30      # a pool's layer is 0.4
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"bf16[{layers},{slots // 2}," in ln]


# ----------------------------------------- power retention, the cell's widths
@pytest.mark.parametrize("entry", ["decode_step", "chunked"])
def test_the_retention_kernels_compile_at_the_cells_widths(one_chip, entry):
    """``brumby-rollout-sat``: 16 rows (a 768-row mixed round in pieces of
    256) against the pool ``[8 layers, 16 + 1 slots, 8 heads, 128, 8320]``
    float32: both Pallas kernels are custom calls by their own names, the
    pool is aliased (held once) and the temporaries stay under 64 MiB: the
    state step's block is a whole head, and the pieces' kernel expands the
    features inside it: no ``[rows, 8320]`` temporary (the XLA form held
    0.98 GB)."""
    from deepspeedsyclsupport_tpu.ops import retention

    cfg = get_config("brumby-14b", num_layers=8)
    h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dim = retention.state_dim(d)
    # the state step copies one KV head's whole state a grid step (4.26 MB
    # in, the same out, double-buffered), and the larger block changes
    # neither the aliasing nor the temporaries asserted below
    assert dim == 8320 and 2 * d * dim * 4 <= retention.STEP_VMEM_BYTES
    pools = [((8, 17, hk, d, dim), jnp.float32),
             ((8, 17, hk, dim), jnp.float32)]
    rows = 16 if entry == "decode_step" else 768
    acts = [((rows, h, d), jnp.bfloat16), ((rows, hk, d), jnp.bfloat16),
            ((rows, hk, d), jnp.bfloat16), ((rows, hk), jnp.float32)]
    if entry == "decode_step":
        def f(q, k, v, gam, s, z, slots, fresh):
            return retention.decode_step(
                q, k, v, gam, (s, z), 3, slots, fresh, cfg,
                retention.STATE_STEPS["pallas"])

        last = [((16,), jnp.int32), ((16,), jnp.bool_)]
    else:
        def f(q, k, v, gam, s, z, row0, length, slot, fresh, count):
            return retention.chunked(
                q, k, v, gam, (s, z), 3, (row0, length, slot, fresh, count),
                cfg, retention.PIECE_CARRIES["pallas"])

        last = [((20,), jnp.int32)] * 3 + [((20,), jnp.bool_),
                                           ((), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in acts + pools + last]
    compiled = jax.jit(f, donate_argnums=(4, 5)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    name = {"decode_step": "ret_state_step", "chunked": "ret_piece"}[entry]
    assert "tpu_custom_call" in text and name in text
    pool_bytes = 8 * 17 * hk * (d + 1) * dim * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes


# ------------------------- two attention kinds, two pools, the cell's widths
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_two_kind_forwards_compile_at_the_cells_widths(one_chip, program):
    """Both serving forwards of ``command-a-plus`` at the cell's widths and
    shapes (ONE period of four layers, 16 of 128 experts held; 24 sequences,
    768 rows, contexts to 66,560, both whole pools): 128 query heads over 8
    KV heads of 128 compile as atoms of 64 rows (``default_atom_rows``: at
    128 rows one grid step models at 81 MiB of VMEM) and one-row tiles; the
    kernels are custom calls under a name a KIND (``paged_swa_*`` on the
    windowed layers' pool and table, ``paged_full_*`` on the full layer's),
    the kinds' scopes reach the compiled text, all four pools are aliased to
    the result, ``argument_size`` is what the configuration's file
    says is resident, 13.17 GiB, and the temporaries stay under 0.35 GiB
    (0.31; 0.71 while each layer's ``wq`` was sliced out of the ``[in,
    out]`` stack and turned, 640 MiB of it)."""
    from benchmark import scopes
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (
        window_blocks_a_sequence)
    from deepspeedsyclsupport_tpu.ops.paged_attention import default_atom_rows

    engine = RaggedInferenceConfig(
        block_size=64, num_blocks=12288, max_sequences=24,
        max_tokens_per_batch=768, max_context=66560)
    assert (default_atom_rows(128, 128, 8, 128, 64, 2), engine.blocks_per_seq,
            window_blocks_a_sequence(4096, engine)) == (64, 1040, 77)
    compiled, kv, _params = _cell_forward(one_chip, "command-a-plus-ep8-d4",
                                          program)
    assert kv.k.shape == (1, 12288 * 64, 8, 128)
    assert kv.wk.shape == (3, 24 * 77 * 64, 8, 128)
    kernels = {"paged_swa_decode", "paged_full_decode"} | (
        {"paged_swa_prefill", "paged_full_prefill"}
        if program == "ragged_forward" else set())
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert kernels <= calls
    assert not calls & {"paged_decode", "ragged_prefill"}
    under = scopes.instructions_under(text, ("attn_swa", "attn_full",
                                             "moe_shared"))
    assert set(under.values()) == {"attn_swa", "attn_full", "moe_shared"}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * (kv.k.size + kv.wk.size) * 2
    assert round(m.argument_size_in_bytes / 2**30, 2) == 13.17
    assert m.temp_size_in_bytes < 0.35 * 2**30


# ----------------------------------- the gated delta rule, the cell's widths
@pytest.mark.parametrize("entry", ["decode_step", "chunked"])
def test_the_delta_rule_compiles_at_the_cells_widths(one_chip, entry):
    """``solar2-agent-sat``: 256 rows (a 768-row mixed round in pieces of
    64) against the pool ``[3 layers, 256 + 1 slots, 64 heads, 128, 128]``
    float32 (3.0 GiB): each entry is ONE custom call by its own name (the
    state step 16 heads a grid step; the pieces' kernel over a 768-row
    round's 269 pieces), the pool is aliased (held once) in both, and the
    chunked form holds under 128 MiB of temporaries and turns no ``[768,
    64, 128]`` operand in XLA: the rows go into the kernel token-major as
    they come."""
    from deepspeedsyclsupport_tpu.ops import kda

    cfg = get_config("solar-open2")
    h, d = cfg.kda_num_heads, cfg.kda_head_dim
    assert (h, d, cfg.kda_chunk_size, kda.STEP_HEADS) == (64, 128, 64, 16)
    pool = ((3, 257, h, d, d), jnp.float32)
    rows = 256 if entry == "decode_step" else 768
    acts = [((rows, h, d), jnp.float32)] * 4 + [((rows, h), jnp.float32)]
    if entry == "decode_step":
        def f(q, k, v, g, beta, s, slots, fresh):
            return kda.decode_step(q, k, v, g, beta, s, 1, slots, fresh, cfg,
                                   kda.STATE_STEPS["pallas"])

        last = [((rows,), jnp.int32), ((rows,), jnp.bool_)]
    else:
        def f(q, k, v, g, beta, s, row0, length, slot, fresh, count):
            return kda.chunked(q, k, v, g, beta, s, 1,
                               (row0, length, slot, fresh, count), cfg,
                               kda.PIECES["pallas"])

        last = [((269,), jnp.int32)] * 3 + [((269,), jnp.bool_),
                                            ((), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in acts + [pool] + last]
    compiled = jax.jit(f, donate_argnums=5).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    name = "kda_state_step" if entry == "decode_step" else "kda_piece"
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and name in calls[0], calls
    assert mem.alias_size_in_bytes >= 3 * 257 * h * d * d * 4
    assert mem.temp_size_in_bytes < 128 << 20, mem.temp_size_in_bytes
    if entry == "chunked":
        assert "kda_scan/kda_chunk" in calls[0]
        turned = [line for line in text.splitlines()
                  if re.search(r"= f32\[(768,64,128|64,768,128|768,8,8,128)\]"
                               r"\S* (copy|transpose)\(", line)]
        assert not turned, turned


# ------------------------- the one-token rows' convolution, tail in place
# layers, slots with the sink, channels, rows, a bias, a mixed round's rows,
# its pieces and their rows
CONV_CELLS = {
    "solar2-agent-sat": (3, 257, 24576, 256, False, 768, 269, 64),
    "nemo3-reason-sat": (12, 129, 6144, 128, True, 512, 133, 128),
}


@pytest.mark.parametrize("entry", ["decode", "mixed"])
@pytest.mark.parametrize("cell", sorted(CONV_CELLS))
def test_the_convolutions_tail_compiles_at_the_cells_widths(one_chip, cell,
                                                            entry):
    """The two cells that run ``ops/ssm.conv_step``, a bfloat16 pool
    ``[layers, 3, slots + 1, channels]``: the tail's kernel is a custom call
    by its own name over blocks of whole sublane tiles of slots (the chip's
    compiler refuses a one-row copy of the pool), the pool is aliased (held
    once) and nothing of its size is a temporary, alone (``decode``) and
    behind the pieces' kernel (``mixed``: ``conv_pieces``, one call over
    all of a round's pieces, which reads and writes the same pool on other
    slots in blocks of the pool's own layout: NO copy of the pool, where
    the loop of XLA it replaced turned the whole pool taps-minor once a
    piece, and no ``[T + chunk, channels]`` float32 updated a piece)."""
    from deepspeedsyclsupport_tpu.ops import ssm

    layers, slots, c, rows, bias, t, pieces, chunk = CONV_CELLS[cell]
    held = layers * 3 * slots * c * 2
    assert ssm.conv_tile(rows, c) == (16, min(c, 8192))
    # all of the channels a grid step, strips of 256 lanes
    assert ssm.conv_pieces_tile(c, ssm.piece_frame(chunk), 2, 4) == (c,
                                                                     256)

    def f(x, w, b, conv, at, keep, row0, length, slot, fresh, count, dec):
        b = b if bias else None
        if entry == "mixed":
            out, conv = ssm.conv_pieces(
                x, w, b, conv, 1, (row0, length, slot, fresh, count), chunk,
                ssm.CONV_PIECES["pallas"])
            x = x[dec]
        one, conv = ssm.conv_step(x, w, b, conv, 1, at, keep,
                                  ssm.CONV_STEPS["pallas"])
        return (one if entry == "decode" else out.at[dec].set(one)), conv

    n = rows if entry == "decode" else t
    shapes = [((n, c), jnp.bfloat16), ((4, c), jnp.float32),
              ((c,), jnp.float32), ((layers, 3, slots, c), jnp.bfloat16),
              ((rows,), jnp.int32), ((rows,), jnp.bool_)] \
        + [((pieces,), jnp.int32)] * 3 + [((pieces,), jnp.bool_),
                                          ((), jnp.int32),
                                          ((rows,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(f, donate_argnums=3).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    calls = [ln.split(" = ")[0].split()[-1].split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls == ["%conv_pieces"] * (entry == "mixed") \
        + ["%conv_tail_step"], calls
    assert mem.alias_size_in_bytes >= held
    pool_copies = [ln for ln in text.splitlines() if re.search(
        rf"= bf16\[{layers},3,{slots},{c}\]\S* copy(-start)?\(", ln)]
    assert not pool_copies, pool_copies
    if entry == "decode":
        # the token's float32 copy and the row at each slot: no pool
        assert mem.temp_size_in_bytes < held // 2, mem.temp_size_in_bytes
    else:
        # the results once (aliased to the zeros they are written over) and
        # the one-token rows' operands: not the pool, not [T + chunk,
        # channels] float32 beside the results
        assert mem.temp_size_in_bytes < min(held, (t + chunk) * c * 4), \
            mem.temp_size_in_bytes
        assert not re.search(rf"f32\[{t + chunk},{c}\]", text)


# ------------------- blocks chosen from pooled keys beside a lightning state
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_sala_forwards_compile_and_copy_no_pool(one_chip, program):
    """Both serving forwards of ``minicpm-sala`` WHOLE at the cell's widths
    and shapes (twelve layers and their feed-forward parts; 8 sequences, 768
    rows, contexts to 99,072, the whole pool of 12,544 pages a layer): the
    selection, the atoms under its mask and the one-token rows over their
    own page tables are custom calls under their own names (``bsa_select``,
    ``bsa_prefill``, ``bsa_rows``; ``decode_forward``, all one-token rows,
    holds no atom) and the lightning state step Mamba-2's kernel, the scopes
    reach the compiled text, K, V, the pooled keys and the state are aliased
    to the result and none of them is copied, and no sequence's ``[heads,
    rows, windows]`` probabilities stand whole: a tile at a time (0.10 GiB
    of temporaries in ``ragged_forward`` since the atoms take their
    selection a block; 0.74 while its mask of keys stood in HBM, PRs 59-67;
    1.58 while the three lightning stacks ``[9, 4096, 4096]`` were laid out
    transposed once a forward)."""
    from benchmark import scopes

    compiled, kv, _params = _cell_forward(one_chip, "minicpm-sala-d12",
                                          program)
    blocks, seqs = 12544, 8
    assert kv.k.shape == (3, blocks * 64, 2, 128)
    assert kv.ck.shape == (3, blocks, 4, 2, 128)
    assert kv.la_s.shape == (9, seqs + 1, 32, 128, 128)
    kernels = {"bsa_select", "bsa_rows", "ssm_state_step"} | (
        {"bsa_prefill"} if program == "ragged_forward" else set())
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert calls == kernels
    labels = ("bsa_pool", "bsa_score", "bsa_select", "bsa_attend", "bsa_rows",
              "la_proj", "la_gate", "la_step", "la_chunk", "attn_gate")
    under = set(scopes.instructions_under(text, labels).values())
    assert under >= set(labels) - (
        {"bsa_attend", "la_chunk"} if program == "decode_forward" else set())
    m = compiled.memory_analysis()
    held = (2 * kv.k.size + kv.ck.size) * 2 + kv.la_s.size * 4
    assert m.alias_size_in_bytes >= held
    # a tile's probabilities [32, 128, 6192] float32 are 0.1 GB; all of a
    # chunk's at once would be 0.6, the atoms' mask of keys 0.38 twice
    assert m.temp_size_in_bytes < 2**28, m.temp_size_in_bytes
    assert not [ln for ln in text.splitlines() if " copy(" in ln and (
        f"bf16[3,{blocks * 64}," in ln or f"bf16[3,{blocks},4," in ln
        or f"f32[9,{seqs + 1},32," in ln)]


# ------------- attention heads and a Mamba-2 mixer side by side in a layer
@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_side_by_side_forwards_compile_at_the_cells_widths(one_chip,
                                                               program):
    """Both serving forwards of ``falcon-h1-34b`` at ``falconh1-chat-sat``'s
    widths and shapes, the six layers whole (48 sequences, 768 rows,
    contexts to 4,608, the pool of 3,520 pages and 49 state slots behind
    every layer; 13.52 GiB of arguments): the paged kernels at FIVE query
    heads a KV group, Mamba-2's state step with a ``[layer, slot]`` block of
    4 MiB (2 groups x 256 x 2,048 float32) and the tail's kernel at 5,120
    channels are custom calls under their own names and keep the scope they
    were traced under (``h1_attn``, ``ssm_scan``, ``ssm_conv``), K, V, the
    state and the tails are aliased to the result and none of them is
    copied, and the temporaries stay under a GiB (0.04 GiB)."""
    from benchmark import scopes

    compiled, kv, _params = _cell_forward(one_chip, "falcon-h1-34b-d6",
                                          program)
    blocks, seqs = 3520, 48
    assert kv.k.shape == (6, blocks * 64, 4, 128)
    assert kv.ssm.shape == (6, seqs + 1, 2, 256, 2048)
    assert kv.conv.shape == (6, 3, seqs + 1, 5120)
    kernels = {"paged_decode", "ssm_state_step", "conv_tail_step"} | (
        {"ragged_prefill"} if program == "ragged_forward" else set())
    text = compiled.as_text()
    calls = {ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert calls == kernels
    labels = ("h1_attn", "lm_head", "ssm_proj", "ssm_conv", "ssm_scan",
              "ssm_gate", "ssm_chunk")
    under = scopes.instructions_under(text, labels)
    assert set(under.values()) == set(labels) - (
        {"ssm_chunk"} if program == "decode_forward" else set())
    # a custom call keeps its scope: the readers find the kernels by it
    for call, label in (("paged_decode", "h1_attn"),
                        ("ssm_state_step", "ssm_scan"),
                        ("conv_tail_step", "ssm_conv")):
        assert {v for k, v in under.items()
                if k.split(".")[0] == call} == {label}, call
    m = compiled.memory_analysis()
    held = 2 * kv.k.size * 2 + kv.ssm.size * 4 + kv.conv.size * 2
    assert m.alias_size_in_bytes >= held
    assert round(m.argument_size_in_bytes / 2**30, 2) == 13.52
    assert m.temp_size_in_bytes < 2**30, m.temp_size_in_bytes
    assert not [ln for ln in text.splitlines() if " copy(" in ln and (
        f"bf16[6,{blocks * 64}," in ln or f"f32[6,{seqs + 1},2," in ln
        or f"bf16[6,3,{seqs + 1}," in ln)]
