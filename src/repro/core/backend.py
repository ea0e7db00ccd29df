"""Execution backends.

The scheduling core is backend-agnostic (DESIGN.md §2): the engine calls
``decode`` / ``prefill`` / ``copy_out`` / ``copy_in`` and charges time from
the platform cost model. ``SimBackend`` is a no-op data plane (pure
discrete-event simulation — the benchmark harness). ``JaxBackend`` runs real
JAX compute against a real paged KV cache with the Pallas kernels, used by
integration tests and the serving example; it validates that the scheduler's
block accounting is coherent with an actual data plane (offloaded caches
really leave the device and come back bit-exact).
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kvcache.paged import PagedKVCache
from repro.models import model as M


class SimBackend:
    """Cost-model-only backend (the default for benchmarks)."""

    def prefill(self, reqs):
        pass

    def decode(self, reqs):
        pass

    def copy_out(self, req):
        pass

    def copy_in(self, req):
        pass

    def copy_blocks(self, src, dst, device=0):
        pass

    def promote_blocks(self, host_blocks, gpu_blocks):
        pass

    def offload_blocks(self, gpu_blocks, host_blocks):
        pass

    def invalidate(self, rid):
        pass

    def generated_tokens(self, rid):
        """Decoded token ids for a request, or None when this backend
        does not materialize tokens (pure simulation — the serving front
        door synthesizes deterministic placeholder ids instead; see
        launch/http_server.py)."""
        return None


def _bucket(n: int) -> int:
    """Next power of two ≥ n — pad batch/table shapes so the jitted decode
    step compiles once per bucket instead of re-tracing every batch."""
    return 1 << (max(n, 1) - 1).bit_length()


def paged_prefill_chunks(cfg, params, cache, entries, chunk: int = 32):
    """Chunked, bucketed, batched suffix-only paged prefill — THE prefill
    data plane (used by JaxBackend and measured as-is by prefill_bench).

    ``entries``: list of (blocks, tokens, cached) per request — the block
    table, the full target cache-token list, and the leading token count
    already resident in the pool (shared prefix). ``cached`` is **token**-
    granular, not block-granular: with the radix prefix index a request
    can branch off a shared prompt mid-block, in which case its table
    holds the shared full blocks followed by a COW-forked partial block
    whose first ``cached % block_size`` positions are already valid. The
    suffix then starts at an arbitrary in-block offset — ``write_window``
    and the absolute ``q_pos`` coordinates handle that natively. Computes
    and writes only ``tokens[cached:]`` per request, ``chunk`` tokens per
    jitted launch, shapes padded to power-of-two buckets. Mutates ``cache.k/v`` (the
    jitted step donates the pools). Returns the final-suffix-position
    hidden row per entry (None when the suffix is empty)."""
    suffix = [toks[cached:] for _, toks, cached in entries]
    last_h = [None] * len(entries)
    s_max = max((len(s) for s in suffix), default=0)
    if s_max == 0:
        return last_h
    bs = cache.block_size
    bb = _bucket(len(entries))
    pb = _bucket(max(len(blocks) for blocks, _, _ in entries))
    tables = np.zeros((bb, pb), np.int32)
    for i, (blocks, _, _) in enumerate(entries):
        tables[i, :len(blocks)] = blocks
    jtables = jnp.asarray(tables)
    C = min(chunk, _bucket(s_max))
    pp = (C - 1) // bs + 2      # max pages a C-token window can straddle
    for c0 in range(0, s_max, C):
        with obs.span("backend.prefill_chunk", chunk=C) as sp:
            tok = np.zeros((bb, C), np.int32)
            qpos = np.full((bb, C), -1, np.int32)
            # write windows: destination pages in order + first in-page
            # offset + valid count per row (scratch-page padded — see
            # kv_chunk_write)
            wpages = np.full((bb, pp), cache.scratch_block, np.int32)
            wstart = np.zeros((bb,), np.int32)
            wcount = np.zeros((bb,), np.int32)
            for i, (blocks, toks, cached) in enumerate(entries):
                n = min(len(suffix[i]) - c0, C)
                if n <= 0:
                    continue
                tok[i, :n] = suffix[i][c0:c0 + n]
                qpos[i, :n] = cached + c0 + np.arange(n)
                wpages[i], wstart[i] = cache.write_window(
                    blocks, cached + c0, n, pp)
                wcount[i] = n
            sp.set(rows=int(np.count_nonzero(wcount)))
            h, cache.k, cache.v = M.paged_prefill_step(
                cfg, params, cache.k, cache.v, jnp.asarray(tok), jtables,
                jnp.asarray(qpos), jnp.asarray(wpages), jnp.asarray(wstart),
                jnp.asarray(wcount))
            for i, s in enumerate(suffix):
                if c0 <= len(s) - 1 < c0 + C:
                    last_h[i] = h[i, len(s) - 1 - c0]
    return last_h


class JaxBackend:
    """Real compute: tiny model, real paged KV, real host offload.

    Each engine request maps to a row in a bucketed batch of block tables.
    One decode iteration is a single jitted step
    (``models.model.paged_decode_step``): layer-scanned forward over
    stacked params, Pallas batched KV token-write, Pallas paged attention.
    There is no per-request Python anywhere in the write or attend path.
    """

    def __init__(self, cfg, engine_cfg, platform, key=None):
        self.cfg = cfg
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.params = M.init_params(cfg, self.key)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.cache = PagedKVCache(cfg, engine_cfg.gpu_blocks,
                                  platform.block_tokens,
                                  host_blocks=engine_cfg.host_blocks,
                                  dtype=dtype,
                                  host_precision=(
                                      engine_cfg.temporal.kv_precision))
        self.block_tokens = platform.block_tokens
        self.generated: Dict[str, List[int]] = {}
        # tokens actually resident in the paged cache per request (the
        # engine's context_len is only refreshed at quantum boundaries)
        self.cache_len: Dict[str, int] = {}
        # block ids the prefill was written into: a mismatch with the
        # request's current blocks means the request was preempted and
        # re-admitted with fresh (uninitialized) blocks -> re-prefill.
        # copy_in refreshes the signature so offload->upload round trips
        # (same KV, new block ids) do NOT trigger recompute.
        self._prefill_sig: Dict[str, Tuple[int, ...]] = {}
        # prompts that exceeded their block allocation lose KV — never
        # silent: counted here and surfaced as a warning (the engine sizes
        # admissions to the full prompt, so this firing means a bug)
        self.truncated_prompt_tokens = 0
        # final-position prefill logits per request (inspection / tests)
        self.last_prefill_logits: Dict[str, np.ndarray] = {}
        # suffix tokens per jitted prefill launch (bucketed)
        self.prefill_chunk = 32

    # -- engine hooks ----------------------------------------------------------
    def decode(self, reqs):
        with obs.span("backend.decode"):
            reqs = [r for r in reqs if r.num_gpu_blocks > 0]
            if not reqs:
                return
            need = [r for r in reqs if self._needs_prefill(r)]
            if need:
                # batched suffix prefill serves archs whose layer body the
                # paged scan reproduces exactly: dense, and moe now that
                # padded rows are pinned to the sentinel expert (see
                # decoder._paged_ffn / moe_ffn's pad_mask); window/ssm/
                # cross-attn archs still take the per-request path
                if self.cfg.arch_type in ("dense", "moe") \
                        and self.cfg.sliding_window is None:
                    self._prefill_batch(need)
                else:
                    for r in need:
                        self._prefill_one(r)
            self._decode_batch(reqs)

    def _needs_prefill(self, r) -> bool:
        sig = self._prefill_sig.get(r.rid)
        return sig is None or tuple(r.gpu_blocks[:len(sig)]) != sig

    def copy_blocks(self, src: List[int], dst: List[int], device: int = 0):
        """Engine hook: COW clone of shared prefix blocks (device-local).
        Like copy_out/copy_in, this backend materializes device 0 only;
        TP mirror copies on other devices are accounting-only here."""
        if device == 0:
            with obs.span("backend.copy_blocks", blocks=len(src)):
                self.cache.copy_blocks(src, dst)

    def promote_blocks(self, host_blocks: List[int], gpu_blocks: List[int]):
        """Engine hook: host-tier prefix promotion — materialize the
        host-saved KV of a prefix hit into freshly allocated pool pages
        (all layers in one ``block_scatter_layers`` launch per tensor,
        the same H2D data plane request uploads ride)."""
        with obs.span("backend.promote_blocks", blocks=len(gpu_blocks)):
            self.cache.upload(host_blocks, gpu_blocks)

    def offload_blocks(self, gpu_blocks: List[int], host_blocks: List[int]):
        """Engine hook: session-tier D2H save — move a finished turn's KV
        blocks (which no live request owns) to host pages, the same
        device→host data plane ``copy_out`` uses for stalled requests."""
        with obs.span("backend.offload_blocks", blocks=len(gpu_blocks)):
            self.cache.offload(gpu_blocks, host_blocks)

    def generated_tokens(self, rid: str) -> Optional[List[int]]:
        """Decoded token ids so far — the serving front door's streaming
        source (``/generate`` chunks are cut from this list as it grows
        between engine steps)."""
        gen = self.generated.get(rid)
        return list(gen) if gen is not None else None

    def invalidate(self, rid: str):
        """Engine hook: the request's device blocks were released (evicted)
        or the request finished. Drop the cache bookkeeping so a future
        re-admission re-prefills even if the allocator hands back the very
        same block ids (LIFO free list makes that the common case, and the
        blocks may have been rewritten by other requests in between).
        ``generated`` survives — it is the decoded output and the
        recompute source."""
        self._prefill_sig.pop(rid, None)
        self.cache_len.pop(rid, None)
        self.last_prefill_logits.pop(rid, None)

    def copy_out(self, req):
        # only the private blocks move; the leading shared-prefix blocks
        # stay resident on device (the engine keeps them pinned and sized
        # host_blocks for the private count only)
        with obs.span("backend.copy_out", rid=req.rid,
                      blocks=len(req.host_blocks)):
            self.cache.offload(req.gpu_blocks[req.shared_prefix_blocks:],
                               req.host_blocks)

    def copy_in(self, req):
        with obs.span("backend.copy_in", rid=req.rid,
                      blocks=len(req.host_blocks)):
            self.cache.upload(req.host_blocks, req.reserved_upload_blocks)
        sig = self._prefill_sig.get(req.rid)
        if sig is not None:
            # post-upload table = resident shared-prefix blocks (which never
            # moved) + the freshly uploaded private blocks
            full = req.gpu_blocks + list(req.reserved_upload_blocks)
            self._prefill_sig[req.rid] = tuple(full[:len(sig)])

    # -- internals --------------------------------------------------------------
    def _prefill_tokens(self, req):
        """Target cache-token list for a (re)prefill, plus the leading
        token count already resident in shared prefix blocks.

        Recompute path (preempted request): reproduce the cache the decode
        path would have built. Decode writes its *input* token's KV at the
        current cache length, so position len(p) holds a duplicate of the
        last prompt token, positions after it hold generated[:-1], and the
        newest generated token is the pending decode input (not yet in
        cache). The backend's generated list can run up to a quantum ahead
        of the engine's accounting (which sized the allocation), so roll
        back tokens that don't fit — greedy decode regenerates them
        identically — instead of truncating the KV layout and
        mis-positioning every later write."""
        toks = [t % self.cfg.vocab_size for t in req.prompt_tokens]
        gen = self.generated.get(req.rid, [])
        cap = len(req.gpu_blocks) * self.block_tokens
        if gen and toks:
            keep = max(cap - len(toks), 0)
            if len(gen) > keep:
                gen = gen[:keep]
                self.generated[req.rid] = list(gen)
            if gen:
                toks = toks + [toks[-1]] + gen[:-1]
        if len(toks) > cap:
            # prompt alone exceeds the block allocation: every later
            # position would be skewed — count and warn, never silent
            dropped = len(toks) - cap
            self.truncated_prompt_tokens += dropped
            warnings.warn(
                f"prefill truncation: {req.rid} drops {dropped} prompt "
                f"tokens ({len(toks)} tokens vs {cap} cache capacity); "
                "admission under-sized the allocation")
            toks = toks[:cap]
        cached = min(getattr(req, "prefix_cached_tokens", 0), len(toks))
        return toks, cached

    def _prefill_batch(self, reqs):
        """Batched chunked suffix-only prefill (the shared-prefix data
        plane). Cached prefix KV is read from the pool through the block
        tables; only each request's uncached suffix is computed and
        written — see ``paged_prefill_chunks``."""
        bs = self.block_tokens
        items = [(r, *self._prefill_tokens(r)) for r in reqs]
        # the logits fetch at the end waits for the prefill's device work
        with obs.span("backend.prefill", rows=len(items), tokens=[
                (cached, len(toks) - cached) for _, toks, cached in items]):
            last_h = paged_prefill_chunks(
                self.cfg, self.params, self.cache,
                [(r.gpu_blocks, toks, cached) for r, toks, cached in items],
                chunk=self.prefill_chunk)
            rows = [i for i, x in enumerate(last_h) if x is not None]
            if rows:
                # pad to the batch bucket so head_logits compiles once per
                # bucket (len(rows) varies per prefill and would retrace)
                stack = [last_h[i] for i in rows]
                stack += [stack[0]] * (_bucket(len(items)) - len(rows))
                logits = M.head_logits(self.cfg, self.params,
                                       jnp.stack(stack))
                arr = np.asarray(logits[:len(rows)], np.float32)
                for j, i in enumerate(rows):
                    self.last_prefill_logits[items[i][0].rid] = arr[j]
        for r, toks, _ in items:
            n_blocks = -(-len(toks) // bs) if toks else 0
            self._prefill_sig[r.rid] = tuple(r.gpu_blocks[:n_blocks])
            self.cache_len[r.rid] = len(toks)

    def _prefill_one(self, req):
        toks, _ = self._prefill_tokens(req)
        batch = {"tokens": jnp.asarray([toks], jnp.int32)}
        if self.cfg.arch_type == "vlm":
            batch["patches"] = jnp.zeros(
                (1, self.cfg.num_patch_tokens, self.cfg.d_model))
        if self.cfg.arch_type == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_frames, self.cfg.d_model))
        with obs.span("backend.prefill", rows=1, tokens=[(0, len(toks))]):
            _, cache = M.prefill(self.cfg, self.params, batch)
            if "k" in cache:
                # cache k: (L, 1, S, Hkv, D) -> write into the paged pool
                self.cache.write_prefill(req.gpu_blocks, cache["k"][:, 0],
                                         cache["v"][:, 0])
        n_blocks = -(-len(toks) // self.block_tokens)
        self._prefill_sig[req.rid] = tuple(req.gpu_blocks[:n_blocks])
        self.cache_len[req.rid] = len(toks)

    def _decode_batch(self, reqs):
        if self.cfg.arch_type in ("ssm", "audio"):
            return  # non-paged decode state handled by dense path in examples
        with obs.span("backend.decode_step"):
            bs = self.block_tokens
            b = len(reqs)
            bb = _bucket(b)
            pb = _bucket(max(len(r.gpu_blocks) for r in reqs))
            with obs.span("backend.decode_prep", rows=b, pages=pb) as sp:
                tables = np.zeros((bb, pb), np.int32)
                positions = np.zeros((bb,), np.int32)
                attn_lens = np.zeros((bb,), np.int32)
                toks = np.zeros((bb,), np.int32)
                # padded and full-capacity rows write into the scratch block
                slots = np.full((bb,), self.cache.scratch_slot, np.int32)
                wrote = np.zeros((b,), bool)
                for i, r in enumerate(reqs):
                    blocks = r.gpu_blocks
                    tables[i, :len(blocks)] = blocks
                    cl = min(self.cache_len.get(r.rid, 0), len(blocks) * bs)
                    prev = self.generated.get(r.rid) or [
                        t % self.cfg.vocab_size for t in r.prompt_tokens[-1:]]
                    toks[i] = prev[-1]
                    positions[i] = cl
                    slots[i] = self.cache.slot_of(blocks, cl)
                    wrote[i] = slots[i] != self.cache.scratch_slot
                    # when the allocated blocks are exactly full the new
                    # token's KV is dropped (scratch write) and it attends
                    # over the existing context only — never over another
                    # request's blocks
                    attn_lens[i] = cl + (1 if wrote[i] else 0)
                sp.set(keys=attn_lens[:b].tolist())
            logits, self.cache.k, self.cache.v = M.paged_decode_step(
                self.cfg, self.params, self.cache.k, self.cache.v,
                jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(positions),
                jnp.asarray(attn_lens), jnp.asarray(slots))
            with obs.span("backend.decode_sync"):
                nxt = np.asarray(jnp.argmax(logits[:b], -1), np.int32)
            for i, r in enumerate(reqs):
                gen = self.generated.get(r.rid)
                if gen is None:
                    gen = self.generated[r.rid] = []
                    obs.event("backend.first_token", rid=r.rid)
                gen.append(int(nxt[i]))
                if wrote[i]:
                    self.cache_len[r.rid] = int(positions[i]) + 1
