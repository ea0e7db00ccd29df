"""The side of a run that holds the chip: the served engine, its warm-up,
and the probes a traced run wraps around the program's layers.

The engine is the program's own: ``repro.launch.serve.build_model_engine``
with the options the configuration file names, behind
``repro.launch.http_server.HttpServer``. Nothing here changes what the
program computes. The probes only time and annotate calls into the
engine, the backend's data-plane hooks and the front door, and are
installed in traced runs alone.
"""
from __future__ import annotations

import functools
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file that holds
    a dense decoder under its published (Hugging Face) key names."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=conf["name"], arch_type="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf["attention_bias"], rope_theta=conf["rope_theta"],
        dtype=conf["torch_dtype"], source=conf["source"])


def build(conf: dict, seed: int):
    """The served engine (weights drawn from ``seed``) and its HTTP
    server, started on a free local port."""
    from repro.core.temporal import TemporalConfig
    from repro.launch.http_server import HttpServer
    from repro.launch.serve import build_model_engine

    kw = dict(conf["engine"])
    if "temporal" in kw:
        kw["temporal"] = TemporalConfig(**kw["temporal"])
    eng = build_model_engine(model_config(conf), seed=seed, **kw)
    srv = HttpServer(engine=eng, **conf.get("server", {}))
    return eng, srv


def _bucket(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def warm(eng, least_tokens: int, most_tokens: int, least_new: int,
         copies: int) -> None:
    """Compile (or load from the persistent cache) every program the
    cell's traffic can reach, and run each once.

    The backend pads a step's batch and block table to powers of two, and
    a prefill's chunk to a power of two up to its chunk size, so the step
    programs are one per (batch bucket, table bucket, chunk); a few eager
    programs around them are one per batch size. Requests of
    ``least_tokens`` to ``most_tokens`` tokens (prompt and output) give
    the table buckets, ``least_new`` new tokens the smallest chunk, the
    engine's ``max_running`` the batch sizes. The block-copy kernels are
    one program per number of blocks moved: ``copies`` is the most a
    cell's traffic moves at once (0: it moves none)."""
    from repro.kernels import ops

    be, bs = eng.backend, eng.backend.block_tokens
    nblk = eng.cfg.gpu_blocks
    tables = []
    t = _bucket(-(-least_tokens // bs))
    while t < 2 * -(-most_tokens // bs):
        tables.append(t)
        t *= 2
    chunks = [be.prefill_chunk]
    while chunks[-1] > least_new and chunks[-1] > 1:
        chunks.append(chunks[-1] // 2)
    shapes = [(b, tables[0], chunks[0])
              for b in range(1, eng.cfg.max_running + 1)]
    shapes += [(b, t, c) for t in tables for c in chunks
               for b in sorted({_bucket(b) for b in
                                range(1, eng.cfg.max_running + 1)})
               if (t, c) != (tables[0], chunks[0])]
    for i, (b, pages, chunk) in enumerate(shapes):
        reqs = []
        for r in range(b):
            blocks = [(r * pages + j) % nblk for j in range(pages)]
            n = pages * bs - bs // 2
            reqs.append(SimpleNamespace(
                rid=f"<warm>/{i}/{r}", gpu_blocks=blocks,
                num_gpu_blocks=pages, prompt_tokens=[1] * n,
                prefix_cached_tokens=n - chunk))
        be.decode(reqs)
        for r in reqs:
            be.invalidate(r.rid)
            be.generated.pop(r.rid, None)
    for m in range(1, min(copies, nblk) + 1):
        # as the cache builds its index vectors: from a list
        idx = jnp.asarray(list(range(m)), jnp.int32)
        for pool in ("k", "v"):
            p = getattr(be.cache, pool)
            staged = ops.block_gather_layers(p, idx)
            setattr(be.cache, pool, ops.block_scatter_layers(p, idx, staged))
    jax.block_until_ready((be.cache.k, be.cache.v))


class Probes:
    """Host spans around the program's layers, for a traced run.

    Each wrapped call is timed on the host clock and annotated in the
    profiler's trace, so the trace can name what the host was doing
    while the device idled. Data-plane hooks are timed to the end of
    their device work (``block_until_ready``)."""

    HOOKS = ("copy_out", "copy_in", "offload_blocks", "promote_blocks",
             "copy_blocks")

    def __init__(self):
        self.spans = []          # (name, start, end) on time.monotonic()
        self.decodes = []        # (start, [keys attended per row])
        self.prefills = []       # (start, [(cached, computed) per row])

    def _wrap(self, name, fn, sync=None):
        spans = self.spans

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
                if sync is not None:
                    sync()
            spans.append((name, t0, time.monotonic()))
            return out
        return wrapped

    def install(self, eng, srv) -> None:
        be = eng.backend
        sync = lambda: jax.block_until_ready((be.cache.k, be.cache.v))  # noqa: E731
        eng.step = self._wrap("engine.step", eng.step)
        bs = be.block_tokens
        decode_batch, prefill_batch = be._decode_batch, be._prefill_batch

        def decodes(reqs):
            t0 = time.monotonic()
            ctx = [min(be.cache_len.get(r.rid, 0), len(r.gpu_blocks) * bs)
                   + 1 for r in reqs]
            out = decode_batch(reqs)
            self.decodes.append((t0, ctx))
            return out

        def prefills(reqs):
            t0 = time.monotonic()
            out = prefill_batch(reqs)
            rows = []
            for r in reqs:
                n = be.cache_len[r.rid]
                c = min(getattr(r, "prefix_cached_tokens", 0), n)
                rows.append((c, n - c))
            self.prefills.append((t0, rows))
            return out
        be._decode_batch, be._prefill_batch = decodes, prefills
        be.decode = self._wrap("backend.decode", be.decode)
        for hook in self.HOOKS:
            setattr(be, hook, self._wrap(f"backend.{hook}",
                                         getattr(be, hook), sync))
        srv.front.poll = self._wrap("frontdoor.poll", srv.front.poll)
        srv.front.submit = self._wrap("frontdoor.submit", srv.front.submit)
