"""Pallas batched KV token-write kernel — the decode write data plane.

Every decode iteration appends one token's K/V per sequence into the paged
pool. Doing that with per-request functional updates costs 2·L·B full-cache
copies per token on an accelerator; this kernel scatters the whole batch in
one pass. Each grid step owns one sequence: the scalar-prefetched *slot id*
(``block_id * block_size + offset``) selects the destination page, the
in-page offset the row replaced inside the fetched block.

The pool is written in place: the kernels take every layer's pool,
(L, N, bs, Hkv*D) with each token row lane-dense, and the layer as a
scalar-prefetch operand, so a page is addressed ``(layer, page)`` and the
caller never slices the pool per layer.

Slot convention: callers mask a write (padded batch row, or a sequence
whose allocated blocks are exactly full) by pointing its slot at a scratch
block the pool reserves past the allocatable range — the write still
happens, but lands in memory nothing reads. This keeps the grid free of
divergent control flow and makes "no room" impossible to corrupt live
blocks (the seed's exact-boundary bug wrote into physical block 0).

Live slots must be distinct blocks per grid step (block ownership gives
this for free); only scratch writes may collide, and their content is
by definition dead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _write_kernel(layer_ref, slots_ref, k_new_ref, v_new_ref, k_in_ref,
                  v_in_ref, k_out_ref, v_out_ref, *, block_size: int):
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_size, 1), 0)
    hit = rows == slots_ref[i] % block_size
    # carry the page through (aliased in/out) with one row replaced
    k_out_ref[...] = jnp.where(hit, k_new_ref[...].astype(k_out_ref.dtype),
                               k_in_ref[...])
    v_out_ref[...] = jnp.where(hit, v_new_ref[...].astype(v_out_ref.dtype),
                               v_in_ref[...])


def _write_kernel_flat(layer_ref, slots_ref, k_new_ref, v_new_ref, k_in_ref,
                       v_in_ref, k_out_ref, v_out_ref):
    """Single-grid-step variant: the whole batch lands as ONE vectorized
    scatter over the layer's slot-flattened pool. Interpret mode (CPU
    validation) pays O(full pool) per grid step / per dynamic ref store,
    so the per-sequence grid is collapsed here; the gridded kernel
    remains the TPU path."""
    layer, slots = layer_ref[0], slots_ref[...]
    nl, n, bs, width = k_in_ref.shape
    k = k_in_ref[...].reshape(nl, n * bs, width)
    v = v_in_ref[...].reshape(nl, n * bs, width)
    k = k.at[layer, slots].set(k_new_ref[...][:, 0].astype(k.dtype))
    v = v.at[layer, slots].set(v_new_ref[...][:, 0].astype(v.dtype))
    k_out_ref[...] = k.reshape(k_in_ref.shape)
    v_out_ref[...] = v.reshape(v_in_ref.shape)


def _chunk_kernel(layer_ref, wpages_ref, wstart_ref, wcount_ref,
                  k_new_ref, v_new_ref, k_in_ref, v_in_ref,
                  k_out_ref, v_out_ref, *, block_size: int):
    """Destination-page-gridded chunk write: grid = (batch, window page).

    One grid step owns ONE destination page — a chunk's consecutive suffix
    tokens land several rows in the same page, and a per-token grid would
    revisit that page across steps (write-back racing the next step's
    aliased prefetch). Here every live page appears exactly once; only
    scratch padding pages repeat, and those steps are pure copies.

    Page row r takes chunk token ``pp*bs - wstart + r`` when that token is
    valid: a one-hot (bs, C) matmul picks the rows (each output row sums
    one token times 1 and zeros, so the copy is exact), and the page keeps
    its own rows elsewhere."""
    b = pl.program_id(0)
    pp = pl.program_id(1)
    c = k_new_ref.shape[0]
    # chunk index of each page row
    j = (pp * block_size - wstart_ref[b]
         + jax.lax.broadcasted_iota(jnp.int32, (block_size, c), 0))
    pick = j == jax.lax.broadcasted_iota(jnp.int32, (block_size, c), 1)
    live = (j[:, :1] >= 0) & (j[:, :1] < wcount_ref[b])     # (bs, 1)

    def land(new_ref, in_ref, out_ref):
        rows = jax.lax.dot_general(
            pick.astype(jnp.float32), new_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(live, rows.astype(out_ref.dtype),
                                 in_ref[...])

    land(k_new_ref, k_in_ref, k_out_ref)
    land(v_new_ref, v_in_ref, v_out_ref)


def _chunk_kernel_flat(layer_ref, wpages_ref, wstart_ref, wcount_ref,
                       k_new_ref, v_new_ref, k_in_ref, v_in_ref, k_out_ref,
                       v_out_ref, *, block_size: int, scratch_slot: int):
    """Single-grid-step variant: reconstruct per-token slots from the page
    windows and land the whole chunk as one vectorized scatter (interpret
    mode pays O(full pool) per grid step). Invalid tokens (chunk/batch
    padding) point at the scratch slot; live slots are distinct."""
    bs = block_size
    layer = layer_ref[0]
    wpages = wpages_ref[...]                       # (B, PP)
    wstart = wstart_ref[...]                       # (B,)
    wcount = wcount_ref[...]                       # (B,)
    bsz, c, width = k_new_ref.shape
    j = jax.lax.broadcasted_iota(jnp.int32, (bsz, c), 1)
    pos = wstart[:, None] + j                      # offset within the window
    pages = jnp.take_along_axis(wpages, pos // bs, axis=1)
    slots = jnp.where(j < wcount[:, None],
                      pages * bs + pos % bs, scratch_slot).reshape(-1)
    nl, n = k_in_ref.shape[:2]
    k = k_in_ref[...].reshape(nl, n * bs, width)
    v = v_in_ref[...].reshape(nl, n * bs, width)
    kn = k_new_ref[...].reshape(bsz * c, width)
    vn = v_new_ref[...].reshape(bsz * c, width)
    k = k.at[layer, slots].set(kn.astype(k.dtype))
    v = v.at[layer, slots].set(vn.astype(v.dtype))
    k_out_ref[...] = k.reshape(k_in_ref.shape)
    v_out_ref[...] = v.reshape(v_in_ref.shape)


def _quant_kernel(x_ref, q_ref, s_ref):
    """One grid step quantizes one block: scale per kv head over the
    (token, dim) plane, symmetric int8 payload."""
    x = x_ref[0].astype(jnp.float32)                   # (bs, Hkv, D)
    amax = jnp.max(jnp.abs(x), axis=(0, 2))            # (Hkv,)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale[None, :, None]), -127, 127)
    q_ref[0] = q.astype(jnp.int8)
    s_ref[0] = scale


def _quant_kernel_flat(x_ref, q_ref, s_ref):
    """Single-grid-step variant: all blocks in one vectorized pass."""
    x = x_ref[...].astype(jnp.float32)                 # (M, bs, Hkv, D)
    amax = jnp.max(jnp.abs(x), axis=(1, 3))            # (M, Hkv)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale[:, None, :, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    q = q_ref[0].astype(jnp.float32)                   # (bs, Hkv, D)
    s = s_ref[0]                                       # (Hkv,)
    x_ref[0] = (q * s[None, :, None]).astype(out_dtype)


def _dequant_kernel_flat(q_ref, s_ref, x_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)                 # (M, bs, Hkv, D)
    s = s_ref[...]                                     # (M, Hkv)
    x_ref[...] = (q * s[:, None, :, None]).astype(out_dtype)


def kv_block_quant(blocks, *, interpret: bool = True, flat: bool = None):
    """Quantize staged KV blocks to int8 with per-(block, kv-head) scales.

    blocks: (M, bs, Hkv, D) float — a gathered staging buffer (the D2H
    offload path quantizes AFTER the gather, so the wire payload is the
    int8 tensor + fp32 scales, half the fp16 bytes).
    returns: (q (M, bs, Hkv, D) int8, scales (M, Hkv) float32) with
    ``scale = max(amax/127, 1e-8)`` over each block's (token, dim) plane.

    ``flat`` selects the single-grid-step kernel; defaults to the
    interpret setting (gridded for Mosaic on TPU, flat for the CPU
    interpreter), as everywhere in this package.
    """
    m, bs, hkv, d = blocks.shape
    if flat is None:
        flat = interpret
    out_shape = [jax.ShapeDtypeStruct((m, bs, hkv, d), jnp.int8),
                 jax.ShapeDtypeStruct((m, hkv), jnp.float32)]

    if flat:
        return pl.pallas_call(
            _quant_kernel_flat, out_shape=out_shape, interpret=interpret,
        )(blocks)

    return pl.pallas_call(
        _quant_kernel,
        grid=(m,),
        in_specs=[pl.BlockSpec((1, bs, hkv, d), lambda i: (i, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, bs, hkv, d), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((1, hkv), lambda i: (i, 0))],
        out_shape=out_shape,
        interpret=interpret,
    )(blocks)


def kv_block_dequant(q, scales, out_dtype=jnp.float32,
                     *, interpret: bool = True, flat: bool = None):
    """Dequantize int8 KV blocks back to ``out_dtype``.

    q: (M, bs, Hkv, D) int8; scales: (M, Hkv) float32. The H2D promotion
    path dequantizes INTO the staging buffer before the pool scatter, so
    the device pool stays full-precision and the attention hot loop is
    untouched by the host tier's precision.
    """
    m, bs, hkv, d = q.shape
    if flat is None:
        flat = interpret
    out_shape = jax.ShapeDtypeStruct((m, bs, hkv, d), out_dtype)

    if flat:
        kernel = functools.partial(_dequant_kernel_flat, out_dtype=out_dtype)
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret,
        )(q, scales)

    kernel = functools.partial(_dequant_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=[pl.BlockSpec((1, bs, hkv, d), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, hkv), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, bs, hkv, d), lambda i: (i, 0, 0, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(q, scales)


def kv_chunk_write(k_pages, v_pages, k_new, v_new, wpages, wstart, wcount,
                   layer, *, interpret: bool = True, flat: bool = None):
    """Scatter one suffix chunk per sequence into layer ``layer`` of the
    paged KV pool.

    k_pages/v_pages: (L, N, bs, Hkv*D) — every layer's pool (incl. scratch,
                     the last page, which also pads ``wpages``)
    k_new/v_new:     (B, C, Hkv, D)  — the batch's chunk K/V
    wpages:          (B, PP) int32   — destination pages of each row's
                     write window, in order (scratch-padded)
    wstart:          (B,) int32      — in-page offset of the row's first
                     token inside wpages[:, 0]
    wcount:          (B,) int32      — valid tokens per row (0 = padded row)
    layer:           (1,) int32      — the layer written; every other
                     layer is left as it was
    returns: (k_pages, v_pages) updated (aliased in place when compiled).

    ``flat`` selects the single-grid-step kernel; defaults to the
    interpret setting. The gridded path is TPU-safe for multi-token-per-
    page writes (unlike a per-token grid — see ``_chunk_kernel``).
    """
    nl, n, bs, width = k_pages.shape
    b, c = k_new.shape[0], k_new.shape[1]
    k_new = k_new.reshape(b, c, width)
    v_new = v_new.reshape(b, c, width)
    pp = wpages.shape[1]
    if flat is None:
        flat = interpret
    out_shape = [jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                 jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]

    if flat:
        kernel = functools.partial(_chunk_kernel_flat, block_size=bs,
                                   scratch_slot=(n - 1) * bs)
        return pl.pallas_call(
            kernel, out_shape=out_shape,
            input_output_aliases={6: 0, 7: 1},
            interpret=interpret,
        )(layer, wpages, wstart, wcount, k_new, v_new, k_pages, v_pages)

    kernel = functools.partial(_chunk_kernel, block_size=bs)
    page_spec = pl.BlockSpec(
        (None, None, bs, width),
        lambda b_, p_, ly, wp, ws, wc: (ly[0], wp[b_, p_], 0, 0))
    new_spec = pl.BlockSpec((None, c, width),
                            lambda b_, p_, ly, wp, ws, wc: (b_, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, pp),
            in_specs=[new_spec, new_spec, page_spec, page_spec],
            out_specs=[page_spec, page_spec],
        ),
        out_shape=out_shape,
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )(layer, wpages, wstart, wcount, k_new, v_new, k_pages, v_pages)


def kv_token_write(k_pages, v_pages, k_new, v_new, slots, layer,
                   *, interpret: bool = True, flat: bool = None):
    """Scatter one new token per sequence into layer ``layer`` of the
    paged KV pool.

    k_pages/v_pages: (L, N, bs, Hkv*D) — every layer's pool
    k_new/v_new:     (B, Hkv, D)     — the batch's new-token K/V
    slots:           (B,) int32      — absolute slot ids (block*bs + offset)
    layer:           (1,) int32      — the layer written; every other
                     layer is left as it was
    returns: (k_pages, v_pages) updated (aliased in place when compiled).

    ``flat`` selects the single-grid-step kernel (in-kernel write loop);
    defaults to the interpret setting.
    """
    nl, n, bs, width = k_pages.shape
    b = k_new.shape[0]
    k_new = k_new.reshape(b, 1, width)
    v_new = v_new.reshape(b, 1, width)
    if flat is None:
        flat = interpret
    out_shape = [jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                 jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]

    if flat:
        return pl.pallas_call(
            _write_kernel_flat, out_shape=out_shape,
            input_output_aliases={4: 0, 5: 1},
            interpret=interpret,
        )(layer, slots, k_new, v_new, k_pages, v_pages)

    kernel = functools.partial(_write_kernel, block_size=bs)
    page_spec = pl.BlockSpec((None, None, bs, width),
                             lambda i, ly, s: (ly[0], s[i] // bs, 0, 0))
    new_spec = pl.BlockSpec((None, 1, width), lambda i, ly, s: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[new_spec, new_spec, page_spec, page_spec],
            out_specs=[page_spec, page_spec],
        ),
        out_shape=out_shape,
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(layer, slots, k_new, v_new, k_pages, v_pages)
