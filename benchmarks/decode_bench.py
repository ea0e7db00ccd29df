"""Decode data-plane microbenchmark — jitted scanned step vs seed eager loop.

The seed ``JaxBackend`` decoded with an un-jitted Python loop over layers
and a per-request scalar KV write (``cache.k.at[l, bid, off].set``), i.e.
2·L·B full-cache functional updates per token. The rebuilt hot path is one
jitted program: layer-scanned forward over stacked params, Pallas batched
KV token-write, Pallas paged attention, bucketed shapes so each batch
bucket compiles once.

This benchmark wall-clocks both paths on identical state and reports
tokens/sec and the speedup (acceptance: >= 5x at batch >= 8), plus a
numerical-equality check of the produced logits so the speedup is not
bought with divergence.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import CsvWriter
from repro.configs.base import get_smoke_config
from repro.core.costmodel import A100_PCIE
from repro.kvcache.paged import PagedKVCache
from repro.models import layers as L
from repro.models import model as M


def eager_decode_step(cfg, params, cache, tokens, tables, lens,
                      block_tokens):
    """The seed data plane, verbatim: python layer loop + per-request
    scalar cache writes. Kept here as the benchmark baseline."""
    x = params["embed"][tokens][:, None, :]
    stacked = params["layers"]
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], stacked)
        xn = L.rms_norm(x, lp["attn_norm"])
        q, k, v = L.qkv_project(cfg, lp, xn)
        pos = lens[:, None]
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        for i in range(tokens.shape[0]):
            bid = tables[i, lens[i] // block_tokens]
            off = lens[i] % block_tokens
            cache.k = cache.k.at[l, bid, off].set(
                k[i, 0].reshape(-1).astype(cache.k.dtype))
            cache.v = cache.v.at[l, bid, off].set(
                v[i, 0].reshape(-1).astype(cache.v.dtype))
        out = cache.decode_attention(l, q[:, 0], tables, lens + 1)
        x = x + L.attn_out(lp, out[:, None])
        if "w1" in lp:
            x = x + L.mlp(lp, L.rms_norm(x, lp["mlp_norm"]))
    h = L.rms_norm(x, params["final_norm"])
    return (h @ params["unembed"])[:, 0]


def _setup(batch, blocks_per_req, block_tokens, cfg):
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    n_blocks = batch * blocks_per_req + 4
    cache = PagedKVCache(cfg, n_blocks, block_tokens)
    rng = np.random.default_rng(0)
    tables = np.arange(batch * blocks_per_req, dtype=np.int32) \
        .reshape(batch, blocks_per_req)
    ctx = (blocks_per_req - 1) * block_tokens + block_tokens // 2
    lens = np.full((batch,), ctx, np.int32)
    toks = rng.integers(0, cfg.vocab_size, batch).astype(np.int32)
    # fill the live context with real KV so attention reads real data
    for i in range(batch):
        k_seq = jax.random.normal(
            jax.random.PRNGKey(i), (cfg.num_layers, ctx,
                                    cfg.num_kv_heads, cfg.head_dim))
        cache.write_prefill(list(tables[i]), k_seq, k_seq * 0.5)
    slots = np.array([tables[i, ctx // block_tokens] * block_tokens
                      + ctx % block_tokens for i in range(batch)], np.int32)
    return params, cache, tables, lens, toks, slots


def _bench(fn, reps):
    fn()                                   # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(csv: CsvWriter, quick: bool = False, json_path: str = None):
    cfg = get_smoke_config("stablelm_3b")
    bt = A100_PCIE.block_tokens
    batches = [8] if quick else [4, 8, 16]
    results = []
    for b in batches:
        params, cache, tables, lens, toks, slots = _setup(b, 3, bt, cfg)
        jt, jtab = jnp.asarray(toks), jnp.asarray(tables)
        jpos, jlens = jnp.asarray(lens), jnp.asarray(lens + 1)
        jslots = jnp.asarray(slots)

        # paged_decode_step DONATES the pools — every consumer below gets
        # its own copy of the initial state
        k0, v0 = cache.k, cache.v
        state = {"k": jnp.array(k0), "v": jnp.array(v0)}

        def jit_step():
            logits, state["k"], state["v"] = M.paged_decode_step(
                cfg, params, state["k"], state["v"], jt, jtab, jpos,
                jlens, jslots)
            return logits

        jit_s = _bench(jit_step, reps=20 if quick else 50)

        ecache = PagedKVCache(cfg, cache.num_blocks, bt)
        ecache.k, ecache.v = jnp.array(k0), jnp.array(v0)

        def eager_step():
            return eager_decode_step(cfg, params, ecache, jt, tables,
                                     lens, bt)

        eager_s = _bench(eager_step, reps=2 if quick else 5)

        # same-state logits must agree (speedup without divergence)
        ref_cache = PagedKVCache(cfg, cache.num_blocks, bt)
        ref_cache.k, ref_cache.v = jnp.array(k0), jnp.array(v0)
        ref = eager_decode_step(cfg, params, ref_cache, jt, tables, lens, bt)
        got, _, _ = M.paged_decode_step(cfg, params, jnp.array(k0),
                                        jnp.array(v0), jt, jtab, jpos,
                                        jlens, jslots)
        # bf16 accumulation order differs (scan + fused writes vs unrolled
        # loop); anything beyond a few ulps would mean real divergence
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=6e-2, rtol=6e-2)

        speedup = eager_s / jit_s
        csv.row(f"decode_jit_b{b}", jit_s * 1e6,
                f"tok_s={b / jit_s:.1f}")
        csv.row(f"decode_eager_b{b}", eager_s * 1e6,
                f"tok_s={b / eager_s:.1f}")
        csv.row(f"decode_speedup_b{b}", 0.0, f"x{speedup:.2f}")
        results.append({"batch": b, "jit_tok_s": b / jit_s,
                        "eager_tok_s": b / eager_s, "speedup": speedup})
    if json_path:
        from benchmarks.common import write_json
        write_json("decode", results, json_path)
    return results


if __name__ == "__main__":
    from benchmarks.common import bench_args
    args = bench_args()
    run(CsvWriter(), quick=args.quick, json_path=args.json)
