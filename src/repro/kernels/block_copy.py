"""Pallas KV-block gather/scatter — the migration data plane (paper §6.3).

Offload: scattered pool blocks are gathered into a contiguous staging buffer
(one DMA-friendly slab) before the host transfer. Upload: the staging buffer
is scattered back into (possibly different) pool blocks. On TPU the gather
rides ``PrefetchScalarGridSpec`` so the source/destination page of each grid
step comes from a scalar-prefetched index vector — the same mechanism the
paged-attention kernel uses for its block tables.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(idx_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def block_gather(pages, indices, *, interpret: bool = True):
    """pages: (N, bs, Hkv, D); indices: (M,) -> staging (M, bs, Hkv, D)."""
    n, bs, hkv, d = pages.shape
    m = indices.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[pl.BlockSpec((1, bs, hkv, d),
                                   lambda i, idx: (idx[i], 0, 0, 0))],
            out_specs=pl.BlockSpec((1, bs, hkv, d),
                                   lambda i, idx: (i, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, bs, hkv, d), pages.dtype),
        interpret=interpret,
    )(indices, pages)


def block_gather_layers(pools, indices, *, interpret: bool = True):
    """All-layer gather: pools (L, N, bs, Hkv, D); indices (M,) int32
    -> staging (L, M, bs, Hkv, D) in one kernel launch (no host loop
    over L — the migration data plane moves a block id's every layer).
    """
    nl, n, bs, hkv, d = pools.shape
    m = indices.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[pl.BlockSpec((1, 1, bs, hkv, d),
                                   lambda l, i, idx: (l, idx[i], 0, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, bs, hkv, d),
                                   lambda l, i, idx: (l, i, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nl, m, bs, hkv, d), pools.dtype),
        interpret=interpret,
    )(indices, pools)


def block_scatter_layers(pools, indices, staging, *, interpret: bool = True):
    """All-layer scatter: write staging (L, M, bs, Hkv, D) into pool blocks
    ``indices`` across every layer at once. Aliased in place when compiled.
    """
    nl, n, bs, hkv, d = pools.shape
    m = indices.shape[0]

    def kernel(idx_ref, staging_ref, pools_in_ref, pools_out_ref):
        pools_out_ref[...] = staging_ref[...]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[
                pl.BlockSpec((1, 1, bs, hkv, d),
                             lambda l, i, idx: (l, i, 0, 0, 0)),
                pl.BlockSpec((1, 1, bs, hkv, d),
                             lambda l, i, idx: (l, idx[i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bs, hkv, d),
                                   lambda l, i, idx: (l, idx[i], 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pools.shape, pools.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices, staging, pools)


def block_gather_quant_layers(pools, indices, *, interpret: bool = True):
    """Fused all-layer gather + int8 quantize — the quantize-on-offload
    data plane: pools (L, N, bs, Hkv, D) float; indices (M,) int32
    -> (staging (L, M, bs, Hkv, D) int8, scales (L, M, Hkv) float32).

    One grid step owns one (layer, block) pair, reads the scattered pool
    page, and emits the int8 payload plus a per-kv-head scale
    (``max(amax/127, 1e-8)`` over the (token, dim) plane) — so the D2H
    copy that follows moves half the fp16 bytes. Gridded-only, like the
    other migration kernels (the grid is the data plane's natural shape;
    interpret mode executes it the same way).
    """
    nl, n, bs, hkv, d = pools.shape
    m = indices.shape[0]

    def kernel(idx_ref, src_ref, q_ref, s_ref):
        x = src_ref[0, 0].astype(jnp.float32)          # (bs, Hkv, D)
        amax = jnp.max(jnp.abs(x), axis=(0, 2))        # (Hkv,)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(x / scale[None, :, None]), -127, 127)
        q_ref[0, 0] = q.astype(jnp.int8)
        s_ref[0, 0, 0] = scale

    # scales carry a unit axis so each step's (1, Hkv) tile spans the
    # array's two trailing dims, as the TPU block-shape rule requires
    qs, scales = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[pl.BlockSpec((1, 1, bs, hkv, d),
                                   lambda l, i, idx: (l, idx[i], 0, 0, 0))],
            out_specs=[pl.BlockSpec((1, 1, bs, hkv, d),
                                    lambda l, i, idx: (l, i, 0, 0, 0)),
                       pl.BlockSpec((1, 1, 1, hkv),
                                    lambda l, i, idx: (l, i, 0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((nl, m, bs, hkv, d), jnp.int8),
                   jax.ShapeDtypeStruct((nl, m, 1, hkv), jnp.float32)],
        interpret=interpret,
    )(indices, pools)
    return qs, scales[:, :, 0]


def block_scatter_dequant_layers(pools, indices, staging, scales,
                                 *, interpret: bool = True):
    """Fused dequantize + all-layer scatter — the promotion/pull delivery
    path: staging (L, M, bs, Hkv, D) int8 + scales (L, M, Hkv) float32
    are expanded back to the pool dtype and written into pool blocks
    ``indices`` across every layer. Aliased in place when compiled, like
    :func:`block_scatter_layers`; the device pool stays full-precision —
    quantization lives only in the host tier and on the wire.
    """
    nl, n, bs, hkv, d = pools.shape
    m = indices.shape[0]

    def kernel(idx_ref, staging_ref, scales_ref, pools_in_ref,
               pools_out_ref):
        q = staging_ref[0, 0].astype(jnp.float32)      # (bs, Hkv, D)
        s = scales_ref[0, 0, 0]                        # (Hkv,)
        pools_out_ref[0, 0] = (q * s[None, :, None]).astype(
            pools_out_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nl, m),
            in_specs=[
                pl.BlockSpec((1, 1, bs, hkv, d),
                             lambda l, i, idx: (l, i, 0, 0, 0)),
                pl.BlockSpec((1, 1, 1, hkv),
                             lambda l, i, idx: (l, i, 0, 0)),
                pl.BlockSpec((1, 1, bs, hkv, d),
                             lambda l, i, idx: (l, idx[i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bs, hkv, d),
                                   lambda l, i, idx: (l, idx[i], 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pools.shape, pools.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(indices, staging, scales[:, :, None], pools)


def block_scatter(pages, indices, staging, *, interpret: bool = True):
    """Write staging (M, bs, Hkv, D) into pool blocks ``indices``.

    Returns the updated pool. Uses input/output aliasing so the pool is
    updated in place on TPU (no full-pool copy).
    """
    n, bs, hkv, d = pages.shape
    m = indices.shape[0]

    def kernel(idx_ref, staging_ref, pages_in_ref, pages_out_ref):
        pages_out_ref[...] = staging_ref[...]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[
                pl.BlockSpec((1, bs, hkv, d), lambda i, idx: (i, 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv, d),
                             lambda i, idx: (idx[i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, hkv, d),
                                   lambda i, idx: (idx[i], 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices, staging, pages)
