"""Pallas chunked paged-prefill attention kernel.

Suffix-only prefill for shared-prefix serving: the queries are one chunk of
C *uncached* suffix tokens per sequence; every key/value lives in the paged
KV pool — the cached prefix blocks AND the just-written suffix blocks are
both addressed through the block table. Query j of sequence b sits at
absolute position ``q_pos[b, j]`` and attends causally over pool positions
``<= q_pos[b, j]`` (its own KV is already in the pool: callers scatter the
chunk's KV via ``kv_chunk_write`` *before* attending, so the kernel needs
no separate in-flight-KV operand and no intra-chunk special case).

Mirrors the paged-decode kernel's structure:

 * it reads the served pool in place — every layer's pool,
   (L, N, bs, Hkv*D) with lane-dense token rows, and the layer as a
   scalar-prefetch operand; a page is fetched as ``(layer, table[b, p])``;
 * gridded TPU path — grid = (batch, page), block-table entries scalar-
   prefetched so the page index map can gather; per-batch flash
   accumulators (m, l, acc) live in VMEM scratch across page iterations.
   Pages past a row's last query position are neither fetched again nor
   computed. Each step transposes the page in VMEM, so its kv heads split
   along sublanes (``(Hkv*D, bs) -> (Hkv, D, bs)``, no lane split) and
   every head does the full (C*G) x (bs) score block: chunked prefill
   gets MXU-sized matmuls instead of decode's single-row GEMVs;
 * flat CPU path — the batch/page loops collapse into in-kernel
   ``fori_loop``s over dynamic ref slices (interpret mode pays O(full
   operand) per grid step, so fewer grid steps win on CPU), with the
   same page math.

Masking convention: ``q_pos = -1`` marks a padded query row (chunk or
batch padding) — every key is masked and the output row is zeros (the
flash finalizer divides by max(l, eps)). Padded *table* entries are only
ever read for positions the mask already rejects.

Correctness oracle: ``repro.kernels.ref.paged_prefill_attention_ref``
(swept in tests/test_kernels.py, flat and gridded, f32 and bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_page(q, k, v, valid, m_prev, l_prev, acc_prev, scale):
    """One page of the flash (online-softmax) update, per kv head.

    q: (Hkv, R, D) query rows of each kv head; k/v: (bs, Hkv*D) one page
    of lane-dense rows; valid: (1, R, bs); m/l: (Hkv, R, 1); acc:
    (Hkv, R, D). All float32, as the scores and weights always were."""
    hkv, _, d = q.shape
    bs = k.shape[0]
    kt = k.astype(jnp.float32).T.reshape(hkv, d, bs)   # heads on sublanes
    vt = v.astype(jnp.float32).T.reshape(hkv, d, bs)
    scores = jax.lax.dot_general(                      # (Hkv, R, bs)
        q, kt, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
    acc = acc_prev * alpha + jax.lax.dot_general(      # (Hkv, R, D)
        probs, vt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _kernel(layer_ref, block_tables_ref, last_ref,   # scalar prefetch
            qpos_ref, q_ref, k_ref, v_ref,           # VMEM blocks
            o_ref,                                   # output block
            m_scr, l_scr, acc_scr,                   # VMEM scratch
            *, block_size: int, num_pages: int, scale):
    """Query rows are (chunk token, group head) pairs flattened to R = C*G,
    so every operand is 3-D like the decode kernel's and the per-row
    position arrives as an (R, 1) column that broadcasts along the lanes
    (Mosaic cannot move a lane vector onto the sublane axis)."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(p * block_size <= last_ref[b])
    def _page():
        kv_pos = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = (kv_pos <= qpos_ref[...])[None]        # (1, R, bs)
        m, l, acc = _attend_page(q_ref[...].astype(jnp.float32),
                                 k_ref[...], v_ref[...], valid, m_scr[...],
                                 l_scr[...], acc_scr[...], scale)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(p == num_pages - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        o_ref[...] = out.astype(o_ref.dtype)


def _kernel_flat(layer_ref, bt_ref, last_ref, qpos_ref, q_ref, k_ref, v_ref,
                 o_ref, *, block_size: int, num_pages: int, batch: int,
                 scale):
    """Single-grid-step variant: batch/page loops as in-kernel fori_loops
    over dynamic ref slices (the CPU-interpret path, as in paged_attention
    and kv_write), with the gridded kernel's page math."""
    layer = layer_ref[0]
    hkv, r, d = q_ref.shape[1:]

    def body_b(b, _):
        q = q_ref[b].astype(jnp.float32)               # (Hkv, R, D)
        qp = qpos_ref[b]                               # (R, 1)
        init = (jnp.full((hkv, r, 1), NEG_INF, jnp.float32),
                jnp.zeros((hkv, r, 1), jnp.float32),
                jnp.zeros((hkv, r, d), jnp.float32))

        def body_p(p, carry):
            blk = bt_ref[b, p]
            kv_pos = p * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_size), 1)
            return _attend_page(q, k_ref[layer, blk], v_ref[layer, blk],
                                (kv_pos <= qp)[None], *carry, scale)

        live = jnp.minimum(num_pages, last_ref[b] // block_size + 1)
        _, l_fin, acc = jax.lax.fori_loop(0, live, body_p, init)
        o_ref[b] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, batch, body_b, 0)


def _kernel_quant(block_tables_ref,                  # scalar prefetch
                  qpos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_scr, l_scr, acc_scr,
                  *, block_size: int, num_pages: int):
    """Dequant-fused variant of ``_kernel``: int8 pools + per-(page,
    kv-head) fp32 scales, expanded right after the VMEM fetch."""
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qp = qpos_ref[0]                                   # (C,) int32
    q = q_ref[0].astype(jnp.float32)                   # (Hkv, C, G, D)
    k = k_ref[0].astype(jnp.float32) * ks_ref[0][None, :, None]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0][None, :, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    scores = jax.lax.dot_general(                      # (Hkv, C, G, bs)
        q, k, (((3,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    kv_pos = p * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, 1, block_size), 3)
    valid = kv_pos <= qp[None, :, None, None]          # (1, C, 1, bs)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev = m_scr[...]                                # (Hkv, C, G, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        probs, v, (((3,), (0,)), ((0,), (1,))),        # (Hkv, C, G, D)
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(p == num_pages - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = out.astype(o_ref.dtype)


def _kernel_quant_flat(bt_ref, qpos_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, *, block_size: int, num_pages: int,
                       batch: int):
    """Flat (CPU-interpret) dequant-fused variant of ``_kernel_flat``."""

    def body_b(b, _):
        q = q_ref[pl.ds(b, 1)][0].astype(jnp.float32)      # (Hkv, C, G, D)
        qp = qpos_ref[pl.ds(b, 1)][0]                      # (C,)
        hkv, c, g, d = q.shape
        scale = 1.0 / jnp.sqrt(jnp.float32(d))
        init = (jnp.full((hkv, c, g, 1), NEG_INF, jnp.float32),
                jnp.zeros((hkv, c, g, 1), jnp.float32),
                jnp.zeros((hkv, c, g, d), jnp.float32))

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
                q, k, (((3,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32) * scale
            kv_pos = p * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, 1, block_size), 3)
            valid = kv_pos <= qp[None, :, None, None]
            scores = jnp.where(valid, scores, NEG_INF)
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            probs = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + probs.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                probs, v, (((3,), (0,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        _, l_fin, acc = jax.lax.fori_loop(0, num_pages, body_p, init)
        out = acc / jnp.maximum(l_fin, 1e-20)
        o_ref[pl.ds(b, 1)] = out.astype(o_ref.dtype)[None]
        return 0

    jax.lax.fori_loop(0, batch, body_b, 0)


def paged_prefill_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                                  block_tables, q_pos,
                                  *, interpret: bool = True,
                                  flat: bool = None):
    """Chunked suffix-prefill attention over an int8-quantized pool.

    q: (B, C, H, D) float; pools: (N, bs, Hkv, D) int8; k_scale/v_scale:
    (N, Hkv) float32; tables: (B, P) int32; q_pos: (B, C) int32 (-1 =
    padded query). Separate entry point so the fp16 hot path keeps its
    exact jit signature and numerics (see ``paged_attention_quant``).

    The engine never calls it, so it keeps a one-layer pool of
    (N, bs, Hkv, D) pages; the served pool's lane-dense, all-layer
    layout is :func:`paged_prefill_attention`'s alone.
    """
    b, c, h, d = q.shape
    n, bs, hkv, _ = k_pages.shape
    p = block_tables.shape[1]
    g = h // hkv
    qt = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4)
    if flat is None:
        flat = interpret

    if flat:
        kernel = functools.partial(_kernel_quant_flat, block_size=bs,
                                   num_pages=p, batch=b)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((b, hkv, c, g, d), q.dtype),
            interpret=interpret,
        )(block_tables, q_pos, qt, k_pages, v_pages, k_scale, v_scale)
        return out.transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)

    kernel = functools.partial(_kernel_quant, block_size=bs, num_pages=p)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, p),
            in_specs=[
                pl.BlockSpec((1, c), lambda b_, p_, bt: (b_, 0)),
                pl.BlockSpec((1, hkv, c, g, d),
                             lambda b_, p_, bt: (b_, 0, 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv, d),
                             lambda b_, p_, bt: (bt[b_, p_], 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv, d),
                             lambda b_, p_, bt: (bt[b_, p_], 0, 0, 0)),
                pl.BlockSpec((1, hkv), lambda b_, p_, bt: (bt[b_, p_], 0)),
                pl.BlockSpec((1, hkv), lambda b_, p_, bt: (bt[b_, p_], 0)),
            ],
            out_specs=pl.BlockSpec((1, hkv, c, g, d),
                                   lambda b_, p_, bt: (b_, 0, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, c, g, 1), jnp.float32),
                pltpu.VMEM((hkv, c, g, 1), jnp.float32),
                pltpu.VMEM((hkv, c, g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, q_pos, qt, k_pages, v_pages, k_scale, v_scale)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, q_pos,
                            layer, *, interpret: bool = True,
                            flat: bool = None):
    """q: (B, C, H, D); pools: (L, N, bs, Hkv*D); tables: (B, P) int32;
    q_pos: (B, C) int32 absolute positions (-1 = padded/masked query);
    layer: (1,) int32, the layer of the pools attended.

    Returns (B, C, H, D). ``flat`` selects the single-grid-step kernel;
    defaults to the interpret setting (gridded for Mosaic on TPU, flat for
    the CPU interpreter).
    """
    b, c, h, d = q.shape
    nl, n, bs, width = k_pages.shape
    hkv = width // d
    g = h // hkv
    r = c * g
    p = block_tables.shape[1]
    # query rows per kv head: (B, Hkv, C*G, D), row = (token, group head)
    qt = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, r, d)
    qpos = jnp.repeat(q_pos, g, axis=1)[:, :, None]   # (B, R, 1)
    last = jnp.max(q_pos, axis=1)                     # (B,) -1: padded row
    scale = 1.0 / float(np.sqrt(d))
    out_shape = jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype)
    if flat is None:
        flat = interpret

    if flat:
        kernel = functools.partial(_kernel_flat, block_size=bs,
                                   num_pages=p, batch=b, scale=scale)
        out = pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret,
            name="paged_prefill_attention",
        )(layer, block_tables, last, qpos, qt, k_pages, v_pages)
    else:
        def page(b_, p_, ly, bt, lt):
            # past the row's last query, hold the last live page
            return ly[0], bt[b_, jnp.minimum(p_, jnp.maximum(lt[b_], 0)
                                             // bs)], 0, 0

        kernel = functools.partial(_kernel, block_size=bs, num_pages=p,
                                   scale=scale)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b, p),
                in_specs=[
                    pl.BlockSpec((None, r, 1),
                                 lambda b_, p_, ly, bt, lt: (b_, 0, 0)),
                    pl.BlockSpec((None, hkv, r, d),
                                 lambda b_, p_, ly, bt, lt: (b_, 0, 0, 0)),
                    pl.BlockSpec((None, None, bs, width), page),
                    pl.BlockSpec((None, None, bs, width), page),
                ],
                out_specs=pl.BlockSpec(
                    (None, hkv, r, d),
                    lambda b_, p_, ly, bt, lt: (b_, 0, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((hkv, r, 1), jnp.float32),
                    pltpu.VMEM((hkv, r, 1), jnp.float32),
                    pltpu.VMEM((hkv, r, d), jnp.float32),
                ],
            ),
            out_shape=out_shape,
            interpret=interpret,
            name="paged_prefill_attention",
        )(layer, block_tables, last, qpos, qt, k_pages, v_pages)
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, d)
