"""AOT TPU (Mosaic) lowering checks for every Pallas kernel entry point.

The suite runs on the CPU sim, where Pallas kernels execute in interpret
mode — which proves numerics but NOT that the Mosaic lowering compiles at
real block sizes (grid specs, SMEM window rules, scalar prefetch, DMA
shapes).  ``jax.export`` cross-platform lowering closes that gap without
hardware: ``export.export(jit(f), platforms=["tpu"])`` runs the full
Pallas→Mosaic lowering pipeline for TPU on any host, failing on exactly the
class of errors a first real-TPU run would hit (the reference counterpart —
compile-testing its CUDA kernels, ``op_builder/builder.py:462`` load path —
happens implicitly at JIT-build time; here it must be explicit).

Caught on day one: the ALiBi slope table was passed as a (1,1)-blocked SMEM
window, which interpret mode accepts but Mosaic rejects on every call (fixed
to a whole-array SMEM ref indexed by head program id).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from deepspeedsyclsupport_tpu.ops.flash_attention import flash_attention
from deepspeedsyclsupport_tpu.ops.paged_attention import (
    paged_decode_attention_pallas, ragged_prefill_attention_pallas)


def lower_tpu(f, *args):
    """Assert f lowers for TPU (full Mosaic pipeline) on abstract avals."""
    exp = export.export(jax.jit(f), platforms=["tpu"])(*args)
    assert "tpu" in exp.platforms
    return exp


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------ flash attention
B, S, H, D = 2, 2048, 16, 128
KVH = 4  # GQA group of 4


def _flash(causal=True, **kw):
    return functools.partial(flash_attention, causal=causal, interpret=False,
                             **kw)


def _grad_of(f, n_args):
    def loss(*args):
        return f(*args).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=tuple(range(n_args)))


class TestFlashLowering:
    def test_fwd_causal(self):
        q = sds((B, S, H, D))
        lower_tpu(_flash(), q, q, q)

    def test_bwd_causal(self):
        q = sds((B, S, H, D))
        lower_tpu(_grad_of(_flash(), 3), q, q, q)

    def test_fwd_bwd_gqa(self):
        q, kv = sds((B, S, H, D)), sds((B, S, KVH, D))
        lower_tpu(_flash(), q, kv, kv)
        lower_tpu(_grad_of(_flash(), 3), q, kv, kv)

    def test_fwd_noncausal(self):
        q = sds((B, S, H, D))
        lower_tpu(_flash(causal=False), q, q, q)

    def test_alibi_fwd_bwd(self):
        q = sds((B, S, H, D))
        slopes = sds((H,), jnp.float32)
        f = lambda q, k, v, a: flash_attention(q, k, v, causal=True, alibi=a,
                                               interpret=False)
        lower_tpu(f, q, q, q, slopes)
        lower_tpu(_grad_of(lambda q, k, v, a: f(q, k, v, a), 3),
                  q, q, q, slopes)

    def test_sliding_window(self):
        q = sds((B, S, H, D))
        lower_tpu(_flash(window=1024), q, q, q)

    def test_segment_ids_packed(self):
        q = sds((B, S, H, D))
        ids = sds((B, S), jnp.int32)
        f = lambda q, k, v, ids: flash_attention(q, k, v, causal=True,
                                                 segment_ids=ids,
                                                 interpret=False)
        lower_tpu(f, q, q, q, ids)

    def test_ragged_packed_kv_positions(self):
        # the v2 packed-KV prefill path: custom positions + separate kv ids
        sq, skv = 512, 4096
        q, kv = sds((B, sq, H, D)), sds((B, skv, KVH, D))
        ids_q, ids_k = sds((B, sq), jnp.int32), sds((B, skv), jnp.int32)
        pos_q, pos_k = sds((B, sq), jnp.int32), sds((B, skv), jnp.int32)

        def f(q, k, v, iq, ik, pq, pk):
            return flash_attention(q, k, v, causal=True, segment_ids=iq,
                                   kv_segment_ids=ik, q_positions=pq,
                                   kv_positions=pk, interpret=False)
        lower_tpu(f, q, kv, kv, ids_q, ids_k, pos_q, pos_k)

    def test_pair_bias_full_fwd_bwd(self):
        # evoformer-style differentiable pair bias, full shape → in-kernel
        # dbias tiles
        s = 1024
        q = sds((B, s, H, D))
        bias = sds((B, H, s, s), jnp.float32)
        f = lambda q, k, v, b: flash_attention(q, k, v, causal=False, bias=b,
                                               interpret=False)
        lower_tpu(f, q, q, q, bias)
        lower_tpu(_grad_of(f, 4), q, q, q, bias)

    def test_pair_bias_broadcast_bwd(self):
        # broadcast pair bias → the dedicated reducing dbias kernel
        s = 1024
        q = sds((4, s, H, D))
        bias = sds((1, H, s, s), jnp.float32)
        f = lambda q, k, v, b: flash_attention(q, k, v, causal=False, bias=b,
                                               interpret=False)
        lower_tpu(_grad_of(f, 4), q, q, q, bias)

    def test_k_bias_mask(self):
        s = 1024
        q = sds((B, s, H, D))
        kb = sds((B, s), jnp.float32)
        f = lambda q, k, v, kb: flash_attention(q, k, v, causal=False,
                                                k_bias=kb, interpret=False)
        lower_tpu(f, q, q, q, kb)

    def test_block_sparse_layout(self):
        # the sparse-attention tile-skip path (SMEM whole-array layout)
        blocks = S // 512
        q = sds((B, S, H, D))
        layout = sds((H, blocks, blocks), jnp.int32)
        f = lambda q, k, v, l: flash_attention(q, k, v, causal=True,
                                               block_layout=l,
                                               interpret=False)
        lower_tpu(f, q, q, q, layout)

    def test_unaligned_seq_pads(self):
        # non-block-multiple sequence → internal padding path
        q = sds((1, 1000, 8, 64))
        lower_tpu(_flash(), q, q, q)

    def test_long_context_8k(self):
        q = sds((1, 8192, H, D))
        lower_tpu(_flash(), q, q, q)


# ----------------------------------------------------- paged/ragged attention
# the paged kernels' K/V operand: one layer's [slots, KVH, D] cache, or the
# serving forwards' whole pool at phi-2's benchmark widths with a traced
# layer index (a Mosaic refusal of ``k_hbm.at[layer, pl.ds(...)]`` shows here)
PAGED_GEOMETRY = {
    "layer_cache": dict(layers=None, slots=8192, bs=128, bps=16, h=H,
                        kvh=KVH, d=D),
    "phi2_whole_pool": dict(layers=32, slots=9600, bs=64, bps=32, h=32,
                            kvh=32, d=128),
}


def _lower_paged(kernel, g, q, *ints):
    """Lower ``kernel(q, cache, cache, *ints)`` at geometry ``g``."""
    cache = (g["slots"], g["kvh"], g["d"])
    if g["layers"] is None:
        f, layer = functools.partial(kernel, block_size=g["bs"]), ()
    else:
        cache = (g["layers"],) + cache
        f = lambda *a: kernel(*a[:-1], block_size=g["bs"],  # noqa: E731
                              layer=a[-1])
        layer = (sds((), jnp.int32),)
    lower_tpu(f, q, sds(cache), sds(cache), *ints, *layer)


class TestPagedLowering:
    SLOTS, BS, BPS = 8192, 128, 16   # kv-cache slots, block size, blocks/seq

    @pytest.mark.parametrize("form", sorted(PAGED_GEOMETRY))
    def test_paged_decode(self, form):
        g = PAGED_GEOMETRY[form]
        s = 64  # sequence slots in the decode batch
        _lower_paged(paged_decode_attention_pallas, g,
                     sds((s, g["h"], g["d"])), sds((s, g["bps"]), jnp.int32),
                     sds((s,), jnp.int32))

    def test_paged_decode_alibi_window(self):
        s = 64
        q = sds((s, H, D))
        kc = sds((self.SLOTS, KVH, D))
        bt = sds((s, self.BPS), jnp.int32)
        sl = sds((s,), jnp.int32)
        slopes = np.linspace(0.1, 1.0, H).astype(np.float32)
        f = functools.partial(paged_decode_attention_pallas,
                              block_size=self.BS, alibi=slopes)
        lower_tpu(f, q, kc, kc, bt, sl)
        f = functools.partial(paged_decode_attention_pallas,
                              block_size=self.BS, window=512)
        lower_tpu(f, q, kc, kc, bt, sl)

    @pytest.mark.parametrize("form", sorted(PAGED_GEOMETRY))
    def test_ragged_prefill(self, form):
        g = PAGED_GEOMETRY[form]
        a, bq = 16, 128  # atoms x tokens-per-atom (SplitFuse chunking)
        _lower_paged(ragged_prefill_attention_pallas, g,
                     sds((a, bq, g["h"], g["d"])),
                     sds((a, g["bps"]), jnp.int32), sds((a,), jnp.int32),
                     sds((a,), jnp.int32))

    def test_ragged_prefill_mha(self):
        a, bq = 8, 256
        q = sds((a, bq, 8, 128))
        kc = sds((self.SLOTS, 8, 128))
        at = sds((a, self.BPS), jnp.int32)
        p0 = sds((a,), jnp.int32)
        ql = sds((a,), jnp.int32)
        f = functools.partial(ragged_prefill_attention_pallas,
                              block_size=self.BS)
        lower_tpu(f, q, kc, kc, at, p0, ql)


def _custom_calls(exp):
    """(kernel name, operand and result types) of every Mosaic custom call
    in an exported module's text."""
    return [(re.search(r'kernel_name = "(\w+)"', ln).group(1),
             ln.rsplit(" : ", 1)[1])
            for ln in exp.mlir_module().splitlines()
            if "@tpu_custom_call" in ln]


@pytest.mark.parametrize("arch", ["plain", "alibi", "window"])
def test_ragged_forward_holds_two_kernel_calls_a_layer(arch):
    """The whole serving program, lowered for TPU with the ``kernel``
    attention: the layer loop's body holds TWO custom calls, the atoms at
    ``atom_q_size`` rows and the one-token chunks through the decode entry
    (``paged_decode``), whose q tile is one row high."""
    import dataclasses

    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import BlockedKV
    from deepspeedsyclsupport_tpu.models import build_model, get_config

    kw = {"plain": {}, "alibi": {"pos_embed": "alibi"},
          "window": {"sliding_window": 96}}[arch]
    cfg = dataclasses.replace(get_config("tiny"), num_layers=3, head_dim=128,
                              num_heads=4, num_kv_heads=2, hidden_size=512,
                              **kw)
    model = build_model(cfg)
    params = M.serving_layout(jax.eval_shape(model.init_params),
                              model.config)
    s, t, bs, bps, bq = 8, 256, 64, 4, 128
    a = s + t // bq + 1
    pool = sds((cfg.num_layers, 40 * bs, cfg.num_kv_heads, 128))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    exp = export.export(M.build_ragged_forward_fn(model, bs, "kernel"),
                        platforms=["tpu"])(
        params, BlockedKV(pool, pool), i32(t), i32(t), i32(t), i32(s, bps),
        i32(s), i32(a, bq), i32(a), i32(a), i32(a, bps), i32(t), i32(s),
        i32(s))
    calls = dict(_custom_calls(exp))
    assert sorted(calls) == ["paged_decode", "ragged_prefill"], calls
    q_tile = f"x{cfg.num_heads}x128x"
    assert f"tensor<{a}x{bq}{q_tile}" in calls["ragged_prefill"]
    assert f"tensor<{s}x1{q_tile}" in calls["paged_decode"]


def _lowered_ragged_forward(preset, pool_row, **overrides):
    """``ragged_forward`` of a tiny ``preset`` through the ``kernel``
    attention, lowered for TPU; ``pool_row``: a pool row's trailing dims."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (BlockedKV,
                                                                MoeCounters)
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model(preset, **overrides)
    cfg = model.config
    params = M.serving_layout(jax.eval_shape(model.init_params),
                              model.config)
    s, t, bs, bps, bq = 8, 256, 64, 4, 128
    a = s + t // bq + 1
    pool = sds((cfg.num_layers, 40 * bs, *pool_row))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    moe = MoeCounters(i32(cfg.num_moe_layers, cfg.num_experts), i32()) \
        if cfg.any_moe else None
    kv = BlockedKV(pool, None if cfg.kv_lora_rank else pool, moe)
    exp = export.export(M.build_ragged_forward_fn(model, bs, "kernel"),
                        platforms=["tpu"])(
        params, kv, i32(t), i32(t), i32(t), i32(s, bps), i32(s), i32(a, bq),
        i32(a), i32(a), i32(a, bps), i32(t), i32(s), i32(s))
    return [(name, sig.split(") -> ")[0].count("tensor<"))
            for name, sig in _custom_calls(exp)]


TINY_WIDTHS = dict(hidden_size=512, num_layers=2, num_heads=4, vocab_size=512)
# (kernel, operands) of every Mosaic call in the program's text, in order:
# four scalar-prefetch arrays, q, the pool's arrays and the slopes
KV_CALLS = [("ragged_prefill", 8), ("paged_decode", 8)]


@pytest.mark.parametrize("preset,more", [
    ("phi-2", dict(intermediate_size=1024, num_kv_heads=4, head_dim=80)),
    ("olmoe-1b-7b", dict(intermediate_size=256, num_kv_heads=4, head_dim=128,
                         num_experts=8, num_experts_per_tok=2))])
def test_a_k_and_v_pool_lowers_to_the_calls_it_had(preset, more):
    """The guard that neither latent attention nor the atoms' wide step
    moved what a K-and-V model's program calls: phi-2's and OLMoE's hold the
    same two custom calls with the same operand counts (K AND V both reach
    each kernel; the V-from-K path is decided at trace time, by the pool),
    the atoms' call now at the step the rule gives its tile (4 heads over 4
    kv heads x 128: a block of K and V is 128 KiB, so eight) and the
    one-row call at one block, as it was."""
    from deepspeedsyclsupport_tpu.ops import paged_attention as pa

    seen = []
    call = pa._tiled_call
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_tiled_call", lambda *a, **kw: (
            seen.append((kw["name"], a[4].shape[1], kw["pages"])),
            call(*a, **kw))[1])
        calls = _lowered_ragged_forward(preset, (4, 128), **TINY_WIDTHS,
                                        **more)
    assert calls == KV_CALLS, calls
    assert set(seen) == {("ragged_prefill", 128, 8), ("paged_decode", 1, 1)}
    assert pa.kv_step_keys(128, 4, 4, 128, 64, 2, False) == 8 * 64


def test_a_latent_pool_lowers_to_calls_with_one_pool_operand():
    """The same two kernels a layer stack (the leading dense layer's, then
    the expert layers'), each with ONE pool operand: no V reaches them."""
    calls = _lowered_ragged_forward(
        "xing4-29b-a4b", (640,), **TINY_WIDTHS, intermediate_size=1024,
        moe_intermediate_size=256, num_experts=8, num_experts_per_tok=2,
        first_k_dense_replace=1, q_lora_rank=128, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    assert calls == [("ragged_prefill", 7), ("paged_decode", 7)] * 2, calls


# -------------------------------------------------- long-context composites
class TestLongContextLowering:
    """The long-context parallel attention paths (ring CP over ppermute,
    Ulysses all-to-all + flash) must cross-lower for TPU at production
    long-sequence shapes — these are the reference's headline-perf paths
    (Ulysses 54% MFU, ``blogs/deepspeed-ulysses/README.md:82``)."""

    def test_ring_attention_8k(self, mesh8):
        import deepspeedsyclsupport_tpu as ds
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)
        from deepspeedsyclsupport_tpu.parallel.ring_attention import (
            ring_attention)

        reset_world_topology()
        topo = ds.build_topology(dp=2, sp=4)
        q = sds((2, 8192, 16, 128))
        lower_tpu(lambda q, k, v: ring_attention(q, k, v, causal=True,
                                                 topology=topo), q, q, q)

    def test_ulysses_gqa_8k(self, mesh8):
        import deepspeedsyclsupport_tpu as ds
        from deepspeedsyclsupport_tpu.comm.topology import (
            reset_world_topology)
        from deepspeedsyclsupport_tpu.parallel.ulysses import (
            ulysses_attention)

        reset_world_topology()
        ds.build_topology(dp=1, sp=4, tp=2)
        q = sds((1, 8192, 16, 128))
        kv = sds((1, 8192, 8, 128))
        lower_tpu(lambda q, k, v: ulysses_attention(q, k, v, causal=True),
                  q, kv, kv)


# ------------------------------------------------------ quantized collectives
class TestQuantizedCollectiveLowering:
    """Cross-lower the explicit-collective (shard_map) comm ops for TPU over
    an 8-way AbstractMesh — the wire programs ZeRO++/1-bit paths emit."""

    def _mesh(self):
        return jax.sharding.AbstractMesh((8,), ("fsdp",))

    def _lower(self, body, in_specs, out_specs, *args):
        from jax.sharding import PartitionSpec  # noqa: F401 (doc pointer)
        f = jax.shard_map(body, mesh=self._mesh(), in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        lower_tpu(f, *args)

    def test_quantized_all_gather(self):
        from jax.sharding import PartitionSpec as P
        from deepspeedsyclsupport_tpu.comm.quantized import (
            quantized_all_gather)
        x = sds((2048, 512), jnp.bfloat16)
        self._lower(lambda v: quantized_all_gather(v, "fsdp"),
                    P("fsdp"), P(), x)

    def test_all_to_all_quant_reduce(self):
        from jax.sharding import PartitionSpec as P
        from deepspeedsyclsupport_tpu.comm.quantized import (
            all_to_all_quant_reduce)
        x = sds((2048, 512), jnp.bfloat16)
        self._lower(lambda v: all_to_all_quant_reduce(v, "fsdp"),
                    P("fsdp"), P("fsdp"), x)

    def test_compressed_allreduce(self):
        from jax.sharding import PartitionSpec as P
        from deepspeedsyclsupport_tpu.comm.quantized import (
            compressed_allreduce)
        x = sds((4096,), jnp.float32)
        e = sds((4096,), jnp.float32)
        self._lower(lambda v, err: compressed_allreduce(v, err, "fsdp"),
                    (P(), P()), (P(), P()), x, e)
