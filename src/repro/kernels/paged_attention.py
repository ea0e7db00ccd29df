"""Pallas TPU paged-decode-attention kernel.

The serving engine's decode hot loop: one query token per sequence attends
over its paged KV cache through a block table (vLLM-style paging, TPU-native
execution). This is the TPU adaptation of PagedAttention (DESIGN.md §2):

 * the kernel reads the served pool in place: every layer's pool,
   (L, N, bs, Hkv*D) with each token row lane-dense (XLA's default
   layout, so no relayout around the call), and the layer as a
   scalar-prefetch operand. A page is fetched as ``(layer, table[b, p])``:
   the step programs never slice or restack the pool per layer;
 * pages are streamed HBM -> VMEM with ``PrefetchScalarGridSpec`` — the
   block-table entries are scalar-prefetched so the page index map can
   depend on them (the TPU equivalent of the CUDA gather);
 * grid = (batch, page): the page axis is the innermost sequential
   dimension, so per-batch flash accumulators live in VMEM scratch across
   page iterations. Pages past a row's context are neither fetched again
   (the index map holds the last live page) nor computed;
 * heads are split without splitting lanes: the query arrives expanded,
   one row per query head holding the head's D values in its kv head's
   lane segment and zeros elsewhere, so one (rows, Hkv*D) x (bs, Hkv*D)
   matmul gives every head's scores and one (rows, bs) x (bs, Hkv*D)
   matmul every head's weighted values; the finalizer keeps each row's
   own segment. The zeros add exact zeros, and softmax and accumulation
   stay in float32.

Correctness oracle: ``repro.kernels.ref.paged_attention_ref`` (validated in
interpret mode on CPU; see tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _expand_heads(q, kv_heads: int):
    """(B, ..., H, D) queries -> (B, ..., G*Hkv, Hkv*D) lane-dense rows.

    Row ``g*Hkv + j`` is query head ``j*G + g`` (kv head ``j``), its D
    values placed in lanes ``[j*D, (j+1)*D)`` and zeros elsewhere — the
    layout of a lane-dense pool row, so a row times a page row sums the
    head's own products and nothing else."""
    *lead, h, d = q.shape
    g = h // kv_heads
    qg = jnp.swapaxes(q.reshape(*lead, kv_heads, g, d), -3, -2)
    eye = np.eye(kv_heads, dtype=bool)[:, :, None]    # (Hkv, Hkv, 1)
    qe = jnp.where(eye, qg[..., :, None, :], jnp.zeros((), q.dtype))
    return qe.reshape(*lead, h, kv_heads * d)


def _head_segments(kv_heads: int, g: int, d: int):
    """(G*Hkv, Hkv*D) float32 mask: 1 where a lane belongs to the row's
    kv head (the rows of :func:`_expand_heads`)."""
    rows = np.arange(g * kv_heads)[:, None] % kv_heads
    return jnp.asarray(rows == np.arange(kv_heads * d)[None, :] // d,
                       jnp.float32)


def _merge_heads(out, kv_heads: int, d: int):
    """(B, ..., G, Hkv*D) kernel output -> (B, ..., H, D) in head order."""
    *lead, g, _ = out.shape
    out = out.reshape(*lead, g, kv_heads, d)
    return jnp.swapaxes(out, -3, -2).reshape(*lead, g * kv_heads, d)


def _attend_page(qe, k, v, valid, m_prev, l_prev, acc_prev, scale):
    """One page of the flash (online-softmax) update on lane-dense rows.

    qe: (R, Hkv*D) expanded query rows; k/v: (bs, Hkv*D) one page;
    valid: (R or 1, bs) keys each row may see; m/l: (R, 1) running max
    and normalizer; acc: (R, Hkv*D) running weighted values. The scores
    take the operands' own precision (bf16 pool rows times bf16 query
    values are exact in float32); everything after is float32."""
    if qe.dtype != k.dtype:
        qe, k = qe.astype(jnp.float32), k.astype(jnp.float32)
    scores = jax.lax.dot_general(                      # (R, bs)
        qe, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # fully-masked pages keep exp() at exactly zero
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
    acc = acc_prev * alpha + jax.lax.dot_general(
        probs, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _own_segments(acc, l, seg, kv_heads: int):
    """Normalize the rows and keep each row's own kv-head segment:
    (G*Hkv, Hkv*D) -> (G, Hkv*D), group g summing rows g*Hkv..(g+1)*Hkv-1,
    whose segments are disjoint (each lane sums one value and zeros)."""
    out = acc / jnp.maximum(l, 1e-20) * seg
    g = out.shape[0] // kv_heads
    return jnp.concatenate(
        [out[i * kv_heads:(i + 1) * kv_heads].sum(axis=0, keepdims=True)
         for i in range(g)], axis=0)


def _kernel(layer_ref, block_tables_ref, context_lens_ref,  # scalar prefetch
            q_ref, seg_ref, k_ref, v_ref,                    # VMEM blocks
            o_ref,                                           # output block
            m_scr, l_scr, acc_scr,                           # VMEM scratch
            *, block_size: int, num_pages: int, kv_heads: int, scale):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = context_lens_ref[b]

    @pl.when(p * block_size < ctx)
    def _page():
        pos = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        m, l, acc = _attend_page(q_ref[...], k_ref[...], v_ref[...],
                                 pos < ctx, m_scr[...], l_scr[...],
                                 acc_scr[...], scale)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(p == num_pages - 1)
    def _finalize():
        o_ref[...] = _own_segments(acc_scr[...], l_scr[...], seg_ref[...],
                                   kv_heads).astype(o_ref.dtype)


def _kernel_flat(layer_ref, bt_ref, cl_ref, q_ref, seg_ref, k_ref, v_ref,
                 o_ref, *, block_size: int, num_pages: int, batch: int,
                 kv_heads: int, scale):
    """Single-grid-step variant: the batch/page loops live inside the
    kernel as ``fori_loop``s over dynamic ref slices. Same math as the
    gridded kernel (``_attend_page``, ``_own_segments``); buffers are
    traversed once instead of once per grid step, which is what interpret
    mode (CPU validation) needs — its emulation costs O(full operand) per
    grid step."""
    layer = layer_ref[0]
    rows, width = q_ref.shape[1:]

    def body_b(b, _):
        qe = q_ref[b]                                   # (R, Hkv*D)
        ctx = cl_ref[b]
        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, width), jnp.float32))

        def body_p(p, carry):
            blk = bt_ref[b, p]
            pos = p * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1)
            return _attend_page(qe, k_ref[layer, blk], v_ref[layer, blk],
                                pos < ctx, *carry, scale)

        live = jnp.minimum(num_pages, pl.cdiv(ctx, block_size))
        _, l_fin, acc = jax.lax.fori_loop(0, live, body_p, init)
        o_ref[b] = _own_segments(acc, l_fin, seg_ref[...],
                                 kv_heads).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, batch, body_b, 0)


def _kernel_quant(block_tables_ref, context_lens_ref,   # scalar prefetch
                  q_ref, k_ref, v_ref, ks_ref, vs_ref,  # VMEM blocks
                  o_ref,                                # output block
                  m_scr, l_scr, acc_scr,                # VMEM scratch
                  *, block_size: int, num_pages: int):
    """Dequant-fused variant of ``_kernel``: the pools are int8 with
    per-(page, kv-head) fp32 scales; the page is expanded to f32 right
    after the VMEM fetch and the flash math is identical from there."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = context_lens_ref[b]
    start = p * block_size

    q = q_ref[0].astype(jnp.float32)                  # (Hkv, G, D)
    k = k_ref[0].astype(jnp.float32) * ks_ref[0][None, :, None]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0][None, :, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    scores = jax.lax.dot_general(                     # (Hkv, G, bs)
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_size), 2)
    valid = pos < ctx                                  # (1, 1, bs)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_scr[...]                                # (Hkv, G, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        probs, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(p == num_pages - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


def _kernel_quant_flat(bt_ref, cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                       o_ref, *, block_size: int, num_pages: int,
                       batch: int):
    """Flat (CPU-interpret) dequant-fused variant of ``_kernel_flat``."""

    def body_b(b, _):
        q = q_ref[pl.ds(b, 1)][0].astype(jnp.float32)      # (Hkv, G, D)
        ctx = cl_ref[b]
        scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
        hkv, g, d = q.shape
        init = (jnp.full((hkv, g, 1), NEG_INF, jnp.float32),
                jnp.zeros((hkv, g, 1), jnp.float32),
                jnp.zeros((hkv, g, d), jnp.float32))

        def body_p(p, carry):
            m_prev, l_prev, acc = carry
            blk = bt_ref[b, p]
            ks = ks_ref[pl.ds(blk, 1)][0]                    # (Hkv,)
            vs = vs_ref[pl.ds(blk, 1)][0]
            k = k_ref[pl.ds(blk, 1)][0].astype(jnp.float32) \
                * ks[None, :, None]
            v = v_ref[pl.ds(blk, 1)][0].astype(jnp.float32) \
                * vs[None, :, None]
            scores = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32) * scale
            pos = p * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block_size), 2)
            valid = pos < ctx
            scores = jnp.where(valid, scores, NEG_INF)
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                probs, v, (((2,), (0,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        _, l_fin, acc = jax.lax.fori_loop(0, num_pages, body_p, init)
        out = acc / jnp.maximum(l_fin, 1e-20)
        o_ref[pl.ds(b, 1)] = out.astype(o_ref.dtype)[None]
        return 0

    jax.lax.fori_loop(0, batch, body_b, 0)


def paged_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                          block_tables, context_lens,
                          *, interpret: bool = True, flat: bool = None):
    """Decode attention over an int8-quantized paged KV pool.

    q: (B, H, D) float; k_pages/v_pages: (N, bs, Hkv, D) int8;
    k_scale/v_scale: (N, Hkv) float32 per-(page, kv-head) scales;
    tables: (B, P); lens: (B,). Dequant is fused into the page fetch —
    the int8 pool is never materialized at full precision. A separate
    entry point (not a flag on :func:`paged_attention`) so the fp16 hot
    path keeps its exact jit signature and numerics.

    The engine never calls it, so it keeps a one-layer pool of
    (N, bs, Hkv, D) pages; the served pool's lane-dense, all-layer
    layout is :func:`paged_attention`'s alone.
    """
    b, h, d = q.shape
    n, bs, hkv, _ = k_pages.shape
    p = block_tables.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    if flat is None:
        flat = interpret

    if flat:
        kernel = functools.partial(_kernel_quant_flat, block_size=bs,
                                   num_pages=p, batch=b)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            interpret=interpret,
        )(block_tables, context_lens, qg, k_pages, v_pages,
          k_scale, v_scale)
        return out.reshape(b, h, d)

    kernel = functools.partial(_kernel_quant, block_size=bs, num_pages=p)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, p),
            in_specs=[
                pl.BlockSpec((1, hkv, g, d),
                             lambda b_, p_, bt, cl: (b_, 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv, d),
                             lambda b_, p_, bt, cl: (bt[b_, p_], 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv, d),
                             lambda b_, p_, bt, cl: (bt[b_, p_], 0, 0, 0)),
                pl.BlockSpec((1, hkv),
                             lambda b_, p_, bt, cl: (bt[b_, p_], 0)),
                pl.BlockSpec((1, hkv),
                             lambda b_, p_, bt, cl: (bt[b_, p_], 0)),
            ],
            out_specs=pl.BlockSpec((1, hkv, g, d),
                                   lambda b_, p_, bt, cl: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, qg, k_pages, v_pages, k_scale, v_scale)
    return out.reshape(b, h, d)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, layer,
                    *, interpret: bool = True, flat: bool = None):
    """q: (B, H, D); pools: (L, N, bs, Hkv*D); tables: (B, P); lens: (B,);
    layer: (1,) int32, the layer of the pools attended.

    ``flat`` selects the single-grid-step kernel (in-kernel loops); it
    defaults to the interpret setting — gridded for Mosaic on TPU, flat
    for the CPU interpreter.
    """
    b, h, d = q.shape
    nl, n, bs, width = k_pages.shape
    hkv = width // d
    g = h // hkv
    p = block_tables.shape[1]
    qe = _expand_heads(q, hkv)                         # (B, G*Hkv, Hkv*D)
    seg = _head_segments(hkv, g, d)
    scale = 1.0 / float(np.sqrt(d))
    out_shape = jax.ShapeDtypeStruct((b, g, width), q.dtype)
    if flat is None:
        flat = interpret

    if flat:
        kernel = functools.partial(_kernel_flat, block_size=bs,
                                   num_pages=p, batch=b, kv_heads=hkv,
                                   scale=scale)
        out = pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret,
            name="paged_attention",
        )(layer, block_tables, context_lens, qe, seg, k_pages, v_pages)
        return _merge_heads(out, hkv, d)

    def page(b_, p_, ly, bt, cl):
        # past the row's context, hold the last live page: no new fetch
        last = jnp.maximum(cl[b_] - 1, 0) // bs
        return ly[0], bt[b_, jnp.minimum(p_, last)], 0, 0

    kernel = functools.partial(_kernel, block_size=bs, num_pages=p,
                               kv_heads=hkv, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, p),
            in_specs=[
                pl.BlockSpec((None, h, width),
                             lambda b_, p_, ly, bt, cl: (b_, 0, 0)),
                pl.BlockSpec((h, width),
                             lambda b_, p_, ly, bt, cl: (0, 0)),
                pl.BlockSpec((None, None, bs, width), page),
                pl.BlockSpec((None, None, bs, width), page),
            ],
            out_specs=pl.BlockSpec((None, g, width),
                                   lambda b_, p_, ly, bt, cl: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, width), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="paged_attention",
    )(layer, block_tables, context_lens, qe, seg, k_pages, v_pages)
    return _merge_heads(out, hkv, d)
