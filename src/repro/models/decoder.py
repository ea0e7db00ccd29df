"""Decoder stacks: dense GQA, MoE, and Hymba-style hybrid layers.

All stacks scan over a leading layer axis of stacked params. Three entry
points per stack:

  * ``forward``      — full-sequence teacher-forced hidden states (training)
  * ``prefill``      — full sequence + returns per-layer caches
  * ``decode_step``  — one token against caches

Cache pytree (attention archs):
  {"k": (L,B,S,Hkv,Dh), "v": (L,B,S,Hkv,Dh)}
plus for ssm/hybrid:
  {"conv": (L,B,W-1,conv_dim), "state": (L,B,H,P,N)}
``cache_len`` (scalar int32, tokens already valid) is passed separately.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.sharding import logical


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer_stack(cfg, key, dtype):
    """Stacked per-layer params for dense / moe / ssm / hybrid stacks."""
    ks = jax.random.split(key, 6)
    nl = cfg.num_layers
    p = {}
    if cfg.arch_type != "ssm":
        p.update(L.init_attn(cfg, ks[0], nl, dtype))
        p["attn_norm"] = jnp.zeros((nl, cfg.d_model), dtype)
    if cfg.arch_type in ("dense", "vlm", "hybrid"):
        p.update(L.init_mlp(cfg, ks[1], nl, dtype))
        p["mlp_norm"] = jnp.zeros((nl, cfg.d_model), dtype)
    if cfg.arch_type == "moe":
        p.update(M.init_moe(cfg, ks[2], nl, dtype))
        p["mlp_norm"] = jnp.zeros((nl, cfg.d_model), dtype)
    if cfg.arch_type in ("ssm", "hybrid"):
        p.update(init_ssm_sub(cfg, ks[3], nl, dtype))
    if cfg.arch_type == "hybrid":
        # per-channel fusion gains for the parallel attn + ssm heads (Hymba)
        p["fuse_attn"] = jnp.ones((nl, cfg.d_model), dtype)
        p["fuse_ssm"] = jnp.ones((nl, cfg.d_model), dtype)
        p["attn_out_norm"] = jnp.zeros((nl, cfg.d_model), dtype)
        p["ssm_out_norm"] = jnp.zeros((nl, cfg.d_model), dtype)
    return p


def init_ssm_sub(cfg, key, nl, dtype):
    sub = S.init_ssm(cfg, key, nl, dtype)
    if cfg.arch_type == "ssm":
        sub["norm"] = jnp.zeros((nl, cfg.d_model), dtype)
    return sub


# ---------------------------------------------------------------------------
# per-layer bodies
# ---------------------------------------------------------------------------

def _ffn(cfg, lp, h):
    """Dense or MoE FFN with pre-norm; returns (delta, aux_loss)."""
    hn = L.rms_norm(h, lp["mlp_norm"])
    if cfg.arch_type == "moe":
        out, aux = M.moe_ffn(cfg, lp, hn)
        return out, aux
    return L.mlp(lp, hn), jnp.float32(0.0)


def _attn_seq(cfg, lp, xn, positions, k_prefix=None, v_prefix=None):
    """Sequence attention (train/prefill). Returns (out, k, v)."""
    q, k, v = L.qkv_project(cfg, lp, xn)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, "batch", "seq", "heads", None)
    k = logical(k, "batch", "seq", "kv_heads", None)
    v = logical(v, "batch", "seq", "kv_heads", None)
    if k_prefix is not None:
        k_all = jnp.concatenate([k_prefix, k], axis=1)
        v_all = jnp.concatenate([v_prefix, v], axis=1)
        k_pos = jnp.arange(k_all.shape[1])
    else:
        k_all, v_all, k_pos = k, v, positions
    out = L.chunked_attention(q, k_all, v_all, positions, k_pos,
                              window=cfg.sliding_window,
                              causal_skip=cfg.prefill_causal_skip)
    return L.attn_out(lp, out), k, v


def _quantize_kv(t):
    """t: (B,S,H,D) -> (int8 values, (B,S,H) f32 scales)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _attn_decode(cfg, lp, xn, cache, cache_len):
    """One-token attention against cache; writes the new KV at cache_len.

    ``cache``: {"k","v"} (+ "k_scale","v_scale" when kv_quant_int8 — the
    int8 KV path halves decode HBM traffic, EXPERIMENTS.md §Perf).
    Returns (out, new_cache_entries dict).
    """
    q, k, v = L.qkv_project(cfg, lp, xn)                 # (B,1,·,·)
    pos = jnp.full((1, 1), cache_len, jnp.int32)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)
    ys = {}
    if cfg.kv_quant_int8:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        k_c = jax.lax.dynamic_update_slice(cache["k"], kq,
                                           (0, cache_len, 0, 0))
        v_c = jax.lax.dynamic_update_slice(cache["v"], vq,
                                           (0, cache_len, 0, 0))
        ks_c = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                            (0, cache_len, 0))
        vs_c = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                            (0, cache_len, 0))
        k_f = k_c.astype(jnp.float32) * ks_c[..., None]
        v_f = v_c.astype(jnp.float32) * vs_c[..., None]
        out = L.decode_attention(q, k_f, v_f, cache_len + 1,
                                 window=cfg.sliding_window)
        out = out.astype(xn.dtype)   # keep the residual stream in bf16
        ys.update(k=k_c, v=v_c, k_scale=ks_c, v_scale=vs_c)
    else:
        k_c = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_len, 0, 0))
        v_c = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_len, 0, 0))
        out = L.decode_attention(q, k_c, v_c, cache_len + 1,
                                 window=cfg.sliding_window)
        ys.update(k=k_c, v=v_c)
    return L.attn_out(lp, out), ys


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

# When True, layer stacks run as an unrolled python loop instead of
# lax.scan. Used by the dry-run's cost extrapolation: XLA's HloCostAnalysis
# counts a while-loop body ONCE regardless of trip count, so the roofline
# derives per-layer flops/bytes from unrolled L=1 and L=2 compiles.
UNROLL = False


def set_unroll(value: bool) -> None:
    global UNROLL
    UNROLL = bool(value)


def stack_scan(body, carry, xs):
    """lax.scan over stacked layer params, or an unrolled loop (see UNROLL)."""
    if not UNROLL:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for l in range(length):
        x_l = jax.tree.map(lambda a: a[l], xs)
        carry, y = body(carry, x_l)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


# Activation rematerialization for the training scan body: saves only the
# per-layer carry, recomputing internals in the backward pass. Enabled by
# the launcher for large-model training (set_remat(True)); off for smoke
# tests where memory is irrelevant and recompute doubles runtime.
REMAT = False


def set_remat(value: bool) -> None:
    global REMAT
    REMAT = bool(value)


def forward(cfg, stacked, x, positions):
    """Training forward. x: (B,S,d) embedded. Returns (hidden, aux_loss)."""

    def body(carry, lp):
        h, aux = carry
        if cfg.arch_type == "ssm":
            h = h + S.ssm_mixer(cfg, lp, L.rms_norm(h, lp["norm"]))
            return (h, aux), None
        xn = L.rms_norm(h, lp["attn_norm"])
        if cfg.arch_type == "hybrid":
            a_out, _, _ = _attn_seq(cfg, lp, xn, positions)
            s_out = S.ssm_mixer(cfg, lp, xn)
            mix = 0.5 * (L.rms_norm(a_out, lp["attn_out_norm"]) * lp["fuse_attn"]
                         + L.rms_norm(s_out, lp["ssm_out_norm"]) * lp["fuse_ssm"])
            h = h + mix
        else:
            a_out, _, _ = _attn_seq(cfg, lp, xn, positions)
            h = h + a_out
        d, aux_i = _ffn(cfg, lp, h)
        h = logical(h + d, "batch", "seq", "embed")
        return (h, aux + aux_i), None

    if REMAT:
        if cfg.remat_policy == "dots":
            # save matmul outputs; recompute only cheap elementwise ops in
            # the backward pass (flops down ~1/4, activation bytes up)
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body_fn = jax.checkpoint(body, policy=policy)
        else:
            body_fn = jax.checkpoint(body)
    else:
        body_fn = body
    (h, aux), _ = stack_scan(body_fn, (x, jnp.float32(0.0)), stacked)
    return h, aux


def prefill(cfg, stacked, x, positions, cache_size: Optional[int] = None):
    """Prefill: returns (hidden, cache). Caches sized to ``cache_size``."""
    B, Sq = x.shape[:2]
    size = cache_size or Sq

    def body(carry, lp):
        h = carry
        ys = {}
        if cfg.arch_type != "ssm":
            xn = L.rms_norm(h, lp["attn_norm"])
            a_out, k, v = _attn_seq(cfg, lp, xn, positions)
            pad = size - k.shape[1]
            if pad > 0:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            if cfg.kv_quant_int8:
                ys["k"], ys["k_scale"] = _quantize_kv(k)
                ys["v"], ys["v_scale"] = _quantize_kv(v)
            else:
                ys["k"], ys["v"] = k, v
        if cfg.arch_type == "ssm":
            out, (conv, state) = S.ssm_mixer(
                cfg, lp, L.rms_norm(h, lp["norm"]), return_cache=True)
            h = h + out
            ys["conv"], ys["state"] = conv, state
            return h, ys
        if cfg.arch_type == "hybrid":
            s_out, (conv, state) = S.ssm_mixer(cfg, lp, xn, return_cache=True)
            ys["conv"], ys["state"] = conv, state
            mix = 0.5 * (L.rms_norm(a_out, lp["attn_out_norm"]) * lp["fuse_attn"]
                         + L.rms_norm(s_out, lp["ssm_out_norm"]) * lp["fuse_ssm"])
            h = h + mix
        else:
            h = h + a_out
        d, _ = _ffn(cfg, lp, h)
        return h + d, ys

    h, cache = stack_scan(body, x, stacked)
    return h, cache


def _paged_ffn(cfg, lp, h, valid=None):
    """FFN sub-block of the paged serving bodies — dense SwiGLU or
    masked MoE.

    The paged bodies operate on bucket-padded batches/chunks, so MoE
    routing must pin padded rows out of the expert dispatch: ``valid``
    (same leading shape as ``h``'s tokens, True = real) feeds
    ``moe_ffn``'s ``pad_mask``, which routes padded rows to a sentinel
    expert that sorts behind every real segment and scatters out of
    bounds. Without the mask, padded rows crowd real tokens out of
    expert capacity and outputs diverge from the dense path
    nondeterministically with bucket size (the pre-fix hazard that kept
    MoE off the batched paged paths). Aux loss is discarded — serving
    runs no optimizer."""
    if "we1" in lp:
        out, _ = M.moe_ffn(cfg, lp, L.rms_norm(h, lp["mlp_norm"]),
                           pad_mask=valid)
        return h + out
    if "w1" in lp:
        return h + L.mlp(lp, L.rms_norm(h, lp["mlp_norm"]))
    return h


def paged_decode(cfg, stacked, x, k_pool, v_pool, tables, positions,
                 attn_lens, slots):
    """Single-token batched decode against the *paged* KV pool.

    The serving hot path: one ``lax.scan`` over stacked layer params, so
    the HLO is O(1) in depth and the whole step jits as one program. The
    pools ride the scan's carry and each layer's kernels index them by
    the layer number that rides along with the params: KV writes go
    through the Pallas batched token-write kernel (no per-request loop)
    and attention through the Pallas paged-attention kernel, both on the
    whole pool in place — nothing slices, relayouts or restacks it per
    layer.

    x:             (B, 1, d) embedded tokens
    k_pool/v_pool: (L, N+1, bs, Hkv*D) paged pools (incl. scratch block)
    tables:        (B, P) int32 block tables (padded rows arbitrary)
    positions:     (B,) int32 rope position of the new token (= cached len)
    attn_lens:     (B,) int32 tokens to attend over (incl. the new token
                   when its write slot is live; 0 for padded rows)
    slots:         (B,) int32 absolute write slot per sequence (scratch
                   slot => masked write)
    Returns (hidden (B, 1, d), k_pool, v_pool).
    """
    from repro.kernels import ops

    pos = positions[:, None]                             # (B, 1)
    valid = (attn_lens > 0)[:, None]                     # (B, 1) real rows

    def body(carry, xs):
        h, kp, vp = carry
        lp, layer = xs
        layer = layer[None]                              # (1,) for prefetch
        xn = L.rms_norm(h, lp["attn_norm"])
        q, k, v = L.qkv_project(cfg, lp, xn)             # (B, 1, ·, ·)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        kp, vp = ops.kv_token_write(kp, vp, k[:, 0], v[:, 0], slots, layer)
        out = ops.paged_attention(q[:, 0], kp, vp, tables, attn_lens, layer)
        h = h + L.attn_out(lp, out[:, None])
        h = _paged_ffn(cfg, lp, h, valid)
        return (h, kp, vp), None

    layers = jnp.arange(k_pool.shape[0], dtype=jnp.int32)
    (h, k_pool, v_pool), _ = stack_scan(body, (x, k_pool, v_pool),
                                        (stacked, layers))
    return h, k_pool, v_pool


def paged_prefill(cfg, stacked, x, k_pool, v_pool, tables, q_pos,
                  wpages, wstart, wcount):
    """One chunk of batched suffix-only prefill against the *paged* pool.

    The shared-prefix data plane: each sequence's cached prefix KV already
    lives in pool blocks (via the prefix store); this computes and writes
    only the C uncached suffix tokens of the chunk, then attends each
    query over prefix + preceding suffix through the block table. Same
    scan-over-stacked-params shape as ``paged_decode`` — the pools ride
    the carry and are indexed in place by layer, writes go through the
    Pallas chunk-write (gridded per destination page), attention through
    the Pallas paged-prefill kernel.

    x:             (B, C, d) embedded suffix-chunk tokens
    k_pool/v_pool: (L, N+1, bs, Hkv*D) paged pools (incl. scratch block)
    tables:        (B, P) int32 block tables (cached prefix + own blocks)
    q_pos:         (B, C) int32 absolute position per query (-1 = padded;
                   padded queries are masked and never written)
    wpages:        (B, PP) int32 destination pages of each row's write
                   window, in order (scratch-page padded)
    wstart:        (B,) int32 in-page offset of the row's first token
    wcount:        (B,) int32 valid tokens per row (0 = padded row)
    Returns (hidden (B, C, d), k_pool, v_pool).
    """
    from repro.kernels import ops

    pos = jnp.maximum(q_pos, 0)                          # rope positions
    valid = q_pos >= 0                                   # (B, C) real queries

    def body(carry, xs):
        h, kp, vp = carry
        lp, layer = xs
        layer = layer[None]                              # (1,) for prefetch
        xn = L.rms_norm(h, lp["attn_norm"])
        q, k, v = L.qkv_project(cfg, lp, xn)             # (B, C, ·, ·)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        kp, vp = ops.kv_chunk_write(kp, vp, k, v, wpages, wstart, wcount,
                                    layer)
        out = ops.paged_prefill_attention(q, kp, vp, tables, q_pos, layer)
        h = h + L.attn_out(lp, out)
        h = _paged_ffn(cfg, lp, h, valid)
        return (h, kp, vp), None

    layers = jnp.arange(k_pool.shape[0], dtype=jnp.int32)
    (h, k_pool, v_pool), _ = stack_scan(body, (x, k_pool, v_pool),
                                        (stacked, layers))
    return h, k_pool, v_pool


def decode_step(cfg, stacked, cache, x, cache_len):
    """One token. x: (B,1,d) embedded. Returns (hidden, new_cache)."""

    def body(carry, xs):
        h = carry
        lp, c = xs
        ys = {}
        if cfg.arch_type == "ssm":
            out, (conv, state) = S.ssm_decode_step(
                cfg, lp, L.rms_norm(h, lp["norm"]), c["conv"], c["state"])
            ys["conv"], ys["state"] = conv, state
            return h + out, ys
        xn = L.rms_norm(h, lp["attn_norm"])
        a_out, kv_ys = _attn_decode(cfg, lp, xn, c, cache_len)
        ys.update(kv_ys)
        if cfg.arch_type == "hybrid":
            s_out, (conv, state) = S.ssm_decode_step(
                cfg, lp, xn, c["conv"], c["state"])
            ys["conv"], ys["state"] = conv, state
            mix = 0.5 * (L.rms_norm(a_out, lp["attn_out_norm"]) * lp["fuse_attn"]
                         + L.rms_norm(s_out, lp["ssm_out_norm"]) * lp["fuse_ssm"])
            h = h + mix
        else:
            h = h + a_out
        d, _ = _ffn(cfg, lp, h)
        return h + d, ys

    h, new_cache = stack_scan(body, x, (stacked, cache))
    return h, new_cache
