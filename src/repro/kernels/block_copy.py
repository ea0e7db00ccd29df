"""Pallas KV-block gather/scatter — the migration data plane (paper §6.3).

Offload: scattered pool blocks are gathered into a contiguous staging buffer
(one DMA-friendly slab) before the host transfer. Upload: the staging buffer
is scattered back into (possibly different) pool blocks. On TPU the gather
rides ``PrefetchScalarGridSpec`` so the source/destination page of each grid
step comes from a scalar-prefetched index vector — the same mechanism the
paged-attention kernel uses for its block tables.

A page is whatever trails the block axis — ``(bs, Hkv*D)`` token rows in
the served pool's lane-dense layout — and every copy kernel moves it
whole, so the kernels serve any page shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(idx_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def _page_spec(lead, page, index):
    """Block of one page: ``lead`` unit axes placed by ``index`` (which
    returns their block indices), then the page's own axes whole."""
    zeros = (0,) * len(page)
    return pl.BlockSpec((1,) * lead + tuple(page),
                        lambda *a: tuple(index(*a)) + zeros)


def block_gather(pages, indices, *, interpret: bool = True):
    """pages: (N, *page); indices: (M,) -> staging (M, *page)."""
    page = pages.shape[1:]
    m = indices.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[_page_spec(1, page, lambda i, idx: (idx[i],))],
            out_specs=_page_spec(1, page, lambda i, idx: (i,)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, *page), pages.dtype),
        interpret=interpret,
    )(indices, pages)


def block_gather_layers(pools, indices, *, interpret: bool = True):
    """All-layer gather: pools (L, N, *page); indices (M,) int32
    -> staging (L, M, *page) in one kernel launch (no host loop
    over L — the migration data plane moves a block id's every layer).
    """
    nl, page = pools.shape[0], pools.shape[2:]
    m = indices.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[_page_spec(2, page, lambda l, i, idx: (l, idx[i]))],
            out_specs=_page_spec(2, page, lambda l, i, idx: (l, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((nl, m, *page), pools.dtype),
        interpret=interpret,
    )(indices, pools)


def block_scatter_layers(pools, indices, staging, *, interpret: bool = True):
    """All-layer scatter: write staging (L, M, *page) into pool blocks
    ``indices`` across every layer at once. Aliased in place when compiled.
    """
    nl, page = pools.shape[0], pools.shape[2:]
    m = indices.shape[0]

    def kernel(idx_ref, staging_ref, pools_in_ref, pools_out_ref):
        pools_out_ref[...] = staging_ref[...]

    dst = _page_spec(2, page, lambda l, i, idx: (l, idx[i]))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[_page_spec(2, page, lambda l, i, idx: (l, i)), dst],
            out_specs=dst,
        ),
        out_shape=jax.ShapeDtypeStruct(pools.shape, pools.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices, staging, pools)


def _head_lanes(width: int, kv_heads: int, h: int):
    """(1, Hkv*D) mask of kv head ``h``'s lanes in a lane-dense row."""
    d = width // kv_heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= h * d) & (lane < (h + 1) * d)


def block_gather_quant_layers(pools, indices, kv_heads: int,
                              *, interpret: bool = True):
    """Fused all-layer gather + int8 quantize — the quantize-on-offload
    data plane: pools (L, N, bs, Hkv*D) float; indices (M,) int32
    -> (staging (L, M, bs, Hkv*D) int8, scales (L, M, Hkv) float32).

    One grid step owns one (layer, block) pair, reads the scattered pool
    page, and emits the int8 payload plus a per-kv-head scale
    (``max(amax/127, 1e-8)`` over the head's (token, dim) plane, its
    lanes of every row) — so the D2H copy that follows moves half the
    fp16 bytes. Gridded-only, like the other migration kernels (the grid
    is the data plane's natural shape; interpret mode executes it the
    same way).
    """
    nl, n, bs, width = pools.shape
    m = indices.shape[0]

    def kernel(idx_ref, src_ref, q_ref, s_ref):
        x = src_ref[0, 0].astype(jnp.float32)          # (bs, Hkv*D)
        ax = jnp.abs(x)
        row = jnp.ones((1, width), jnp.float32)
        scales = jnp.ones((1, kv_heads), jnp.float32)
        head = jax.lax.broadcasted_iota(jnp.int32, (1, kv_heads), 1)
        for h in range(kv_heads):
            lanes = _head_lanes(width, kv_heads, h)
            amax = jnp.max(jnp.where(lanes, ax, 0.0), axis=(0, 1),
                           keepdims=True)               # (1, 1)
            scale = jnp.maximum(amax / 127.0, 1e-8)
            row = jnp.where(lanes, scale, row)
            scales = jnp.where(head == h, scale, scales)
        q = jnp.clip(jnp.round(x / row), -127, 127)
        q_ref[0, 0] = q.astype(jnp.int8)
        s_ref[0, 0] = scales

    # scales carry a unit axis so each step's (1, Hkv) tile spans the
    # array's two trailing dims, as the TPU block-shape rule requires
    qs, scales = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[_page_spec(2, (bs, width),
                                 lambda l, i, idx: (l, idx[i]))],
            out_specs=[_page_spec(2, (bs, width), lambda l, i, idx: (l, i)),
                       _page_spec(2, (1, kv_heads),
                                  lambda l, i, idx: (l, i))],
        ),
        out_shape=[jax.ShapeDtypeStruct((nl, m, bs, width), jnp.int8),
                   jax.ShapeDtypeStruct((nl, m, 1, kv_heads), jnp.float32)],
        interpret=interpret,
    )(indices, pools)
    return qs, scales[:, :, 0]


def block_scatter_dequant_layers(pools, indices, staging, scales,
                                 *, interpret: bool = True):
    """Fused dequantize + all-layer scatter — the promotion/pull delivery
    path: staging (L, M, bs, Hkv*D) int8 + scales (L, M, Hkv) float32
    are expanded back to the pool dtype and written into pool blocks
    ``indices`` across every layer. Aliased in place when compiled, like
    :func:`block_scatter_layers`; the device pool stays full-precision —
    quantization lives only in the host tier and on the wire.
    """
    nl, n, bs, width = pools.shape
    m, kv_heads = indices.shape[0], scales.shape[-1]

    def kernel(idx_ref, staging_ref, scales_ref, pools_in_ref,
               pools_out_ref):
        q = staging_ref[0, 0].astype(jnp.float32)      # (bs, Hkv*D)
        s = scales_ref[0, 0]                           # (1, Hkv)
        head = jax.lax.broadcasted_iota(jnp.int32, (1, kv_heads), 1)
        row = jnp.ones((1, width), jnp.float32)
        for h in range(kv_heads):
            scale = jnp.sum(jnp.where(head == h, s, 0.0), axis=(0, 1),
                            keepdims=True)              # (1, 1)
            row = jnp.where(_head_lanes(width, kv_heads, h), scale, row)
        pools_out_ref[0, 0] = (q * row).astype(pools_out_ref.dtype)

    dst = _page_spec(2, (bs, width), lambda l, i, idx: (l, idx[i]))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[
                _page_spec(2, (bs, width), lambda l, i, idx: (l, i)),
                _page_spec(2, (1, kv_heads), lambda l, i, idx: (l, i)),
                dst,
            ],
            out_specs=dst,
        ),
        out_shape=jax.ShapeDtypeStruct(pools.shape, pools.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(indices, staging, scales[:, :, None], pools)


def block_scatter(pages, indices, staging, *, interpret: bool = True):
    """Write staging (M, *page) into pool blocks ``indices``.

    Returns the updated pool. Uses input/output aliasing so the pool is
    updated in place on TPU (no full-pool copy).
    """
    page = pages.shape[1:]
    m = indices.shape[0]

    def kernel(idx_ref, staging_ref, pages_in_ref, pages_out_ref):
        pages_out_ref[...] = staging_ref[...]

    dst = _page_spec(1, page, lambda i, idx: (idx[i],))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[_page_spec(1, page, lambda i, idx: (i,)), dst],
            out_specs=dst,
        ),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices, staging, pages)
