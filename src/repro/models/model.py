"""Unified model API across all assigned architectures.

    params = init_params(cfg, key)
    specs  = param_specs(cfg)                      # ShapeDtypeStructs only
    loss, aux = loss_fn(cfg, params, batch)        # training
    logits, cache = prefill(cfg, params, batch, cache_size)
    logits, cache = decode_step(cfg, params, cache, tokens, cache_len)

Batch formats (all int32 tokens):
  dense/moe/ssm/hybrid: {"tokens": (B,S), "targets": (B,S)}
  vlm:   + {"patches": (B,P,d)}   (precomputed projected patch embeddings)
  audio: + {"frames": (B,F,d)}    (precomputed post-conv frame embeddings)
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import decoder as D
from repro.models import encdec as ED
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.sharding import logical


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "unembed": L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.arch_type == "audio":
        p.update(ED.init_encdec(cfg, ks[2], dtype))
    else:
        p["layers"] = D.init_layer_stack(cfg, ks[2], dtype)
    return p


def param_specs(cfg):
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens]
    return logical(x, "batch", "seq", "embed")


def _lm_head(cfg, params, h):
    h = L.rms_norm(h, params["final_norm"]) if cfg.arch_type != "audio" else h
    logits = h @ params["unembed"]
    return logical(logits, "batch", "seq", "vocab")


def _assemble_inputs(cfg, params, batch):
    """Returns (x_embedded, positions, loss_mask, enc_out or None)."""
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens)
    if cfg.arch_type == "vlm":
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        pos = jnp.arange(x.shape[1])[None, :]
        mask = jnp.concatenate(
            [jnp.zeros(patches.shape[:2], jnp.float32),
             jnp.ones(tokens.shape, jnp.float32)], axis=1)
        return x, pos, mask, None
    if cfg.arch_type == "audio":
        enc_out = ED.encode(cfg, params, batch["frames"].astype(x.dtype))
        pos = jnp.arange(tokens.shape[1])[None, :]
        return x, pos, jnp.ones(tokens.shape, jnp.float32), enc_out
    pos = jnp.arange(tokens.shape[1])[None, :]
    return x, pos, jnp.ones(tokens.shape, jnp.float32), None


# ---------------------------------------------------------------------------
# training loss
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch):
    """Causal LM loss; returns (loss, metrics)."""
    x, pos, mask, enc_out = _assemble_inputs(cfg, params, batch)
    if cfg.arch_type == "audio":
        h = ED.decode_forward(cfg, params, x, pos, enc_out)
        aux = jnp.float32(0.0)
    else:
        h, aux = D.forward(cfg, params["layers"], x, pos)
    logits = _lm_head(cfg, params, h)
    if cfg.arch_type == "vlm":
        # only text positions carry loss; targets align to text suffix
        n_text = batch["tokens"].shape[1]
        logits = logits[:, -n_text:]
    loss = L.softmax_xent(logits[:, :-1], batch["targets"][:, 1:],
                          mask[:, -logits.shape[1]:][:, 1:])
    total = loss + aux
    return total, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(cfg, params, batch, cache_size: Optional[int] = None):
    """Prefill the cache; returns (last-position logits, cache)."""
    x, pos, _, enc_out = _assemble_inputs(cfg, params, batch)
    size = cache_size or x.shape[1]
    if cfg.arch_type == "audio":
        h, cache = ED.decode_prefill(cfg, params, x, pos, enc_out, size)
    else:
        h, cache = D.prefill(cfg, params["layers"], x, pos, size)
    logits = _lm_head(cfg, params, h[:, -1:])
    return logits, cache


def decode_step(cfg, params, cache, tokens, cache_len):
    """tokens: (B,) int32; cache_len: scalar int32 (valid prefix length)."""
    x = _embed_tokens(cfg, params, tokens[:, None])
    if cfg.arch_type == "audio":
        h, cache = ED.decode_step(cfg, params, cache, x, cache_len)
    else:
        h, cache = D.decode_step(cfg, params["layers"], cache, x, cache_len)
    logits = _lm_head(cfg, params, h)
    return logits[:, 0], cache


# Donating the pools lets XLA chain the in-place Pallas writes instead of
# copying the full KV cache every token. CPU (interpret-mode validation)
# doesn't implement donation and warns; silence just that message.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(2, 3))
def paged_decode_step(cfg, params, k_pool, v_pool, tokens, tables,
                      positions, attn_lens, slots):
    """Jitted batched decode step against the paged KV pool.

    ``cfg`` is static (frozen dataclass), so one compilation is cached per
    (config, batch-bucket, table-bucket) shape — callers pad ``tokens``/
    ``tables``/``slots`` to bucketed shapes to keep the cache small. The
    pools, (L, N+1, bs, Hkv*D), ride the layer scan's carry and each
    layer's Pallas kernels read and write them in place by layer index,
    with no per-request Python anywhere. The pools are DONATED: callers
    must rebind them from the return value.

    Returns (logits (B, V), k_pool, v_pool).
    """
    x = _embed_tokens(cfg, params, tokens[:, None])
    h, k_pool, v_pool = D.paged_decode(
        cfg, params["layers"], x, k_pool, v_pool, tables, positions,
        attn_lens, slots)
    logits = _lm_head(cfg, params, h)
    return logits[:, 0], k_pool, v_pool


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnums=(2, 3))
def paged_prefill_step(cfg, params, k_pool, v_pool, tokens, tables,
                       q_pos, wpages, wstart, wcount):
    """Jitted chunked suffix-prefill step against the paged KV pool.

    ``tokens`` (B, C) is one chunk of each request's uncached suffix;
    ``q_pos`` (B, C) the absolute positions (-1 = padded query);
    ``wpages``/``wstart``/``wcount`` describe each row's write window
    (destination pages in order, first in-page offset, valid token count —
    see ``kernels.kv_write.kv_chunk_write``). Cached prefix KV is read
    from the pool through ``tables`` — only the suffix is computed. One
    compilation per (config, batch/chunk/table bucket), same bucketing
    contract as ``paged_decode_step``. Pools are DONATED: callers must
    rebind them from the return value.

    Returns (hidden (B, C, d), k_pool, v_pool) — callers take the rows
    they need (e.g. the last valid suffix position) through ``head_logits``.
    """
    x = _embed_tokens(cfg, params, tokens)
    h, k_pool, v_pool = D.paged_prefill(
        cfg, params["layers"], x, k_pool, v_pool, tables, q_pos,
        wpages, wstart, wcount)
    return h, k_pool, v_pool


@functools.partial(jax.jit, static_argnames=("cfg",))
def head_logits(cfg, params, h):
    """Final norm + unembed for selected hidden rows. h: (B, d) -> (B, V)."""
    return _lm_head(cfg, params, h[:, None])[:, 0]


# ---------------------------------------------------------------------------
# cache structure (for dry-run specs and engine allocation)
# ---------------------------------------------------------------------------

def cache_specs(cfg, batch_size: int, cache_size: int, frames: int = 0):
    """ShapeDtypeStructs of the decode cache for (batch, cache_size)."""
    dtype = _dtype(cfg)
    nl = cfg.num_layers
    specs = {}
    if cfg.arch_type != "ssm":
        kv = (nl, batch_size, cache_size, cfg.num_kv_heads, cfg.head_dim)
        if cfg.kv_quant_int8:
            specs["k"] = jax.ShapeDtypeStruct(kv, jnp.int8)
            specs["v"] = jax.ShapeDtypeStruct(kv, jnp.int8)
            sc = kv[:-1]
            specs["k_scale"] = jax.ShapeDtypeStruct(sc, jnp.float32)
            specs["v_scale"] = jax.ShapeDtypeStruct(sc, jnp.float32)
        else:
            specs["k"] = jax.ShapeDtypeStruct(kv, dtype)
            specs["v"] = jax.ShapeDtypeStruct(kv, dtype)
    if cfg.arch_type == "audio":
        f = frames or cfg.encoder_frames
        ckv = (nl, batch_size, f, cfg.num_kv_heads, cfg.head_dim)
        specs["cross_k"] = jax.ShapeDtypeStruct(ckv, dtype)
        specs["cross_v"] = jax.ShapeDtypeStruct(ckv, dtype)
    if cfg.arch_type in ("ssm", "hybrid"):
        _, _, conv_dim = S.proj_dims(cfg)
        specs["conv"] = jax.ShapeDtypeStruct(
            (nl, batch_size, cfg.ssm_conv_width - 1, conv_dim), dtype)
        specs["state"] = jax.ShapeDtypeStruct(
            (nl, batch_size, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
    return specs


def cache_bytes(cfg, batch_size: int, cache_size: int) -> int:
    return sum(s.size * s.dtype.itemsize
               for s in jax.tree.leaves(cache_specs(cfg, batch_size,
                                                    cache_size)))
