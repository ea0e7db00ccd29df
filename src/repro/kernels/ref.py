"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel sweep tests assert against
(``tests/test_kernels.py``). They are deliberately simple and quadratic —
no tiling, no online softmax — so that any numerical disagreement points at
the kernel, not the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_pages(pools, layer, head_dim: int):
    """One layer of a served pool, (L, N, bs, Hkv*D) -> (N, bs, Hkv, D):
    each lane-dense row split into its kv heads."""
    nl, n, bs, width = pools.shape
    return pools[layer[0]].reshape(n, bs, width // head_dim, head_dim)


def paged_attention_ref(q, k_pages, v_pages, block_tables, context_lens,
                        layer):
    """Decode attention over layer ``layer`` of a paged KV pool.

    q:            (B, H, D)             — one query token per sequence
    k_pages:      (L, N, bs, Hkv*D)     — every layer's block pool
    v_pages:      (L, N, bs, Hkv*D)
    block_tables: (B, P) int32          — page ids per sequence (padded
                                          arbitrary)
    context_lens: (B,)   int32          — valid tokens per sequence
    layer:        (1,)   int32
    returns:      (B, H, D)
    """
    d = q.shape[-1]
    return _paged_attention_pages(q, layer_pages(k_pages, layer, d),
                                  layer_pages(v_pages, layer, d),
                                  block_tables, context_lens)


def _paged_attention_pages(q, k_pages, v_pages, block_tables, context_lens):
    """Decode attention over one layer's (N, bs, Hkv, D) pages."""
    b, h, d = q.shape
    n, bs, hkv, _ = k_pages.shape
    p = block_tables.shape[1]
    g = h // hkv

    # materialize each sequence's KV: (B, P*bs, Hkv, D)
    k = k_pages[block_tables].reshape(b, p * bs, hkv, d)
    v = v_pages[block_tables].reshape(b, p * bs, hkv, d)

    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qf, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(p * bs)
    mask = pos[None, :] < context_lens[:, None]          # (B, T)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_tables, q_pos,
                                layer):
    """Chunked paged-prefill attention over layer ``layer`` of a paged
    KV pool.

    q:            (B, C, H, D)      — one suffix chunk of queries per
                                      sequence
    k_pages:      (L, N, bs, Hkv*D) — every layer's block pool (prefix +
                                      suffix KV)
    v_pages:      (L, N, bs, Hkv*D)
    block_tables: (B, P) int32      — page ids per sequence (padded
                                      arbitrary)
    q_pos:        (B, C) int32      — absolute position per query; -1 =
                                      padded (fully masked, output row is
                                      zeros)
    layer:        (1,) int32
    returns:      (B, C, H, D)
    """
    d = q.shape[-1]
    return _paged_prefill_attention_pages(
        q, layer_pages(k_pages, layer, d), layer_pages(v_pages, layer, d),
        block_tables, q_pos)


def _paged_prefill_attention_pages(q, k_pages, v_pages, block_tables,
                                   q_pos):
    """Chunked prefill attention over one layer's (N, bs, Hkv, D) pages."""
    b, c, h, d = q.shape
    n, bs, hkv, _ = k_pages.shape
    p = block_tables.shape[1]
    g = h // hkv

    k = k_pages[block_tables].reshape(b, p * bs, hkv, d)
    v = v_pages[block_tables].reshape(b, p * bs, hkv, d)

    qf = q.reshape(b, c, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bckgd,btkd->bckgt", qf, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(p * bs)
    mask = pos[None, None, :] <= q_pos[:, :, None]       # (B, C, T)
    maskx = mask[:, :, None, None, :]
    scores = jnp.where(maskx, scores, -1e30)
    # masked-safe softmax: fully-masked queries produce zeros, not NaN
    m = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.where(maskx, jnp.exp(scores - m), 0.0)
    denom = jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-20)
    out = jnp.einsum("bckgt,btkd->bckgd", probs / denom,
                     v.astype(jnp.float32))
    return out.reshape(b, c, h, d).astype(q.dtype)


def quantize_block_ref(x):
    """Symmetric int8 block quantization, scale per (block, kv head).

    x: (..., bs, Hkv, D) float — any leading block axes. Returns
    (q int8 same shape, scales float32 (..., Hkv)) with
    ``scale = max(amax/127, 1e-8)`` over each block's (token, dim) plane.
    Every quantizing kernel (offload gather, staging quant) must agree
    with this bit-for-bit.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-3, -1))           # (..., Hkv)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None, :, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_block_ref(q, scale, out_dtype=jnp.float32):
    """Inverse of :func:`quantize_block_ref` (up to rounding error
    bounded by scale/2 per element)."""
    return (q.astype(jnp.float32)
            * scale[..., None, :, None]).astype(out_dtype)


def paged_attention_quant_ref(q, k_pages, v_pages, k_scale, v_scale,
                              block_tables, context_lens):
    """Decode attention over int8 pools: dequantize, then the fp oracle.

    k_pages/v_pages: (N, bs, Hkv, D) int8; k_scale/v_scale: (N, Hkv) f32.
    """
    k = dequantize_block_ref(k_pages, k_scale)
    v = dequantize_block_ref(v_pages, v_scale)
    return _paged_attention_pages(q, k, v, block_tables, context_lens)


def paged_prefill_attention_quant_ref(q, k_pages, v_pages, k_scale,
                                      v_scale, block_tables, q_pos):
    """Chunked prefill attention over int8 pools (dequant-then-oracle)."""
    k = dequantize_block_ref(k_pages, k_scale)
    v = dequantize_block_ref(v_pages, v_scale)
    return _paged_prefill_attention_pages(q, k, v, block_tables, q_pos)


def block_gather_quant_layers_ref(pools, indices, kv_heads: int):
    """Fused gather+quantize oracle. pools: (L, N, bs, Hkv*D) float;
    indices: (M,) -> (int8 (L, M, bs, Hkv*D), scales (L, M, Hkv))."""
    x = pools[:, indices]
    q, scales = quantize_block_ref(x.reshape(*x.shape[:-1], kv_heads, -1))
    return q.reshape(x.shape), scales


def block_scatter_dequant_layers_ref(pools, indices, staging, scales):
    """Fused dequantize+scatter oracle (promotion delivery path):
    staging (L, M, bs, Hkv*D) int8, scales (L, M, Hkv)."""
    heads = staging.reshape(*staging.shape[:-1], scales.shape[-1], -1)
    x = dequantize_block_ref(heads, scales, pools.dtype)
    return pools.at[:, indices].set(x.reshape(staging.shape))


def block_gather_ref(pages, indices):
    """Gather pool blocks into a contiguous staging buffer.

    pages:   (N, *page);  indices: (M,) int32  ->  (M, *page)
    """
    return pages[indices]


def block_scatter_ref(pages, indices, staging):
    """Scatter a staging buffer back into pool blocks (upload path)."""
    return pages.at[indices].set(staging)


def block_gather_layers_ref(pools, indices):
    """All-layer gather. pools: (L, N, *page); indices: (M,)."""
    return pools[:, indices]


def block_scatter_layers_ref(pools, indices, staging):
    """All-layer scatter of staging (L, M, *page) into pool blocks."""
    return pools.at[:, indices].set(staging)


def kv_token_write_ref(k_pages, v_pages, k_new, v_new, slots, layer):
    """Batched decode-token write into layer ``layer``. Pools
    (L, N, bs, Hkv*D); new (B, Hkv, D); slots (B,) absolute slot ids
    (block*bs + offset), distinct per batch."""
    nl, n, bs, width = k_pages.shape
    b = k_new.shape[0]
    kf = k_pages.reshape(nl, n * bs, width)
    vf = v_pages.reshape(nl, n * bs, width)
    kf = kf.at[layer[0], slots].set(
        k_new.reshape(b, width).astype(k_pages.dtype))
    vf = vf.at[layer[0], slots].set(
        v_new.reshape(b, width).astype(v_pages.dtype))
    return kf.reshape(k_pages.shape), vf.reshape(v_pages.shape)


def kv_chunk_write_ref(k_pages, v_pages, k_new, v_new, wpages, wstart,
                       wcount, layer):
    """Suffix-chunk write into layer ``layer``. Pools (L, N, bs, Hkv*D);
    new (B, C, Hkv, D); wpages (B, PP) destination pages per row window
    (scratch = page N-1 padding); wstart (B,) in-page offset of the first
    token; wcount (B,) valid tokens per row."""
    nl, n, bs, width = k_pages.shape
    b, c = k_new.shape[0], k_new.shape[1]
    j = jnp.arange(c)[None, :]
    pos = wstart[:, None] + j
    pages = jnp.take_along_axis(wpages, pos // bs, axis=1)
    slots = jnp.where(j < wcount[:, None],
                      pages * bs + pos % bs, (n - 1) * bs).reshape(-1)
    kf = k_pages.reshape(nl, n * bs, width)
    vf = v_pages.reshape(nl, n * bs, width)
    kf = kf.at[layer[0], slots].set(
        k_new.reshape(b * c, width).astype(k_pages.dtype))
    vf = vf.at[layer[0], slots].set(
        v_new.reshape(b * c, width).astype(v_pages.dtype))
    return kf.reshape(k_pages.shape), vf.reshape(v_pages.shape)


def ssd_scan_ref(x, dt, a, b, c, init_state=None):
    """Sequential (non-chunked) SSD recurrence — the gold reference.

    x: (B, S, H, P); dt, a: (B, S, H) f32 (a = dt * A, A < 0);
    b, c: (B, S, N) f32. Returns (y (B,S,H,P) f32, state (B,H,P,N) f32).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    xf = x.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, at, bt, ct = inp
        da = jnp.exp(at)                                 # (B, H)
        state = state * da[..., None, None] + \
            jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt)
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)
    inputs = (xf.swapaxes(0, 1), dt.swapaxes(0, 1), a.swapaxes(0, 1),
              b.swapaxes(0, 1), c.swapaxes(0, 1))
    state, ys = jax.lax.scan(step, init_state, inputs)
    return ys.swapaxes(0, 1), state


def swa_attention_ref(q, k, v, window):
    """Causal sliding-window attention (prefill). q,k,v: (B, S, H, D)."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = (j <= i) & (j > i - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
