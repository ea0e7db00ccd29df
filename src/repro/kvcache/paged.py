"""Paged KV cache: device pools + host pool, driven by the block ids that
``repro.core.block_pool`` hands out.

Layout (per model): k/v pools of shape (L, N+1, bs, Hkv*D) — pages of
``bs`` token rows, each row one token's kv heads side by side. The row is
the minor axis, so XLA's default tiled layout is also the kernels' own
and a multiple of 128 lanes needs no padding (stablelm-3b: 32 x 80 =
2560 = 20 x 128). The step programs hand the kernels the whole pool and
a layer index, and the kernels read and write ``(layer, page)`` in place:
no per-layer slice, relayout or restack. The migration data plane moves
whole (L, bs, Hkv*D) block-columns per block id so one logical block id
covers every layer (that matches vLLM's block granularity accounting
with 3 MiB/block across all layers). The host tier keeps the same
layout, so a block moves between the tiers without a reshape.

Row ``N`` (``scratch_block``) is never handed out by the allocator: it is
the write sink for masked decode writes — padded batch rows, and sequences
whose allocated blocks are exactly full. Pointing dead writes at a real
page keeps the Pallas write kernel branch-free and makes it impossible for
an out-of-room token to corrupt a live block (the seed wrote those into
physical block 0, silently trashing whichever request owned it).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


class PagedKVCache:
    def __init__(self, cfg, num_blocks: int, block_size: int,
                 host_blocks: int = 0, dtype=jnp.bfloat16,
                 host_precision: str = "fp16"):
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.scratch_block = num_blocks          # masked-write sink (row N)
        self.host_precision = host_precision
        nl, hkv, dh = cfg.num_layers, max(cfg.num_kv_heads, 1), \
            max(cfg.head_dim, 1)
        self.kv_heads = hkv
        shape = (nl, num_blocks + 1, block_size, hkv * dh)
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        # host pool is numpy (pinned host memory stand-in); host_blocks=0
        # means the tier is OFF — allocate nothing (the old max(n, 1)
        # phantom block burned a full L*bs*Hkv*D slab per cache and let
        # misrouted offloads silently "succeed" into it)
        self.host_scales_k = self.host_scales_v = None
        if host_blocks <= 0:
            self.host_k = self.host_v = None
        elif host_precision == "int8_host":
            # quantized host tier: int8 payload + per-(block, kv-head)
            # fp32 scales, half the fp16 bytes (the device pool keeps
            # ``dtype`` — precision changes only as blocks cool to host)
            hshape = (nl, host_blocks, block_size, hkv * dh)
            self.host_k = np.zeros(hshape, np.int8)
            self.host_v = np.zeros(hshape, np.int8)
            self.host_scales_k = np.zeros((nl, host_blocks, hkv),
                                          np.float32)
            self.host_scales_v = np.zeros((nl, host_blocks, hkv),
                                          np.float32)
        else:
            hshape = (nl, host_blocks, block_size, hkv * dh)
            self.host_k = np.zeros(hshape, dtype)
            self.host_v = np.zeros(hshape, dtype)

    def _require_host(self, op: str) -> None:
        if self.host_k is None:
            raise RuntimeError(
                f"host tier is disabled (host_blocks=0) but {op} was "
                "reached — the engine must not route offload/upload "
                "traffic to a cache constructed without a host pool")

    @property
    def scratch_slot(self) -> int:
        """Absolute slot id of the masked-write sink (offset 0)."""
        return self.scratch_block * self.block_size

    def slot_of(self, blocks: List[int], pos: int) -> int:
        """Absolute slot id for token position ``pos`` of a request, or the
        scratch slot when the position falls past the allocated blocks."""
        bs = self.block_size
        if 0 <= pos < len(blocks) * bs:
            return blocks[pos // bs] * bs + pos % bs
        return self.scratch_slot

    def write_window(self, blocks: List[int], start: int, count: int,
                     max_pages: int):
        """Suffix-write window for ``count`` consecutive tokens beginning
        at absolute position ``start`` — the prefill write pattern: the
        first token lands at an arbitrary offset inside a block (right
        after the cached prefix) and later tokens spill across block
        boundaries. Returns (pages, in-page offset of the first token):
        the ordered destination pages padded with the scratch block to
        ``max_pages`` (the ``kv_chunk_write`` contract)."""
        bs = self.block_size
        pages = np.full((max_pages,), self.scratch_block, np.int32)
        first = start // bs
        npages = (start % bs + count + bs - 1) // bs
        pages[:npages] = blocks[first:first + npages]
        return pages, start % bs

    # ---- write path ---------------------------------------------------------
    def write_prefill(self, blocks: List[int], k_seq, v_seq):
        """k_seq/v_seq: (L, S, Hkv, D) for one request; scatter into blocks
        across every layer in one kernel launch."""
        bs = self.block_size
        s = k_seq.shape[1]
        n = -(-s // bs)
        pad = n * bs - s
        if pad:
            k_seq = jnp.pad(k_seq, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_seq = jnp.pad(v_seq, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kb = k_seq.reshape(k_seq.shape[0], n, bs, -1)
        vb = v_seq.reshape(v_seq.shape[0], n, bs, -1)
        idx = jnp.asarray(blocks[:n], jnp.int32)
        self.k = ops.block_scatter_layers(self.k, idx,
                                          kb.astype(self.k.dtype))
        self.v = ops.block_scatter_layers(self.v, idx,
                                          vb.astype(self.v.dtype))

    def write_tokens(self, slots, k_toks, v_toks):
        """Batched decode write: k_toks/v_toks (L, B, Hkv, D); slots (B,)
        absolute slot ids (scratch slot = masked). One scatter for every
        (layer, sequence) pair — no Python loop over L or B."""
        nl, nb, bs, width = self.k.shape
        slots = jnp.asarray(slots, jnp.int32)
        # fold layers into the page axis so one kernel call covers (L, B):
        # layer l's block b lives at folded block l*(N+1)+b of layer 0
        kf = self.k.reshape(1, nl * nb, bs, width)
        vf = self.v.reshape(1, nl * nb, bs, width)
        layer_base = (jnp.arange(nl, dtype=jnp.int32) * (nb * bs))[:, None]
        folded = (layer_base + slots[None, :]).reshape(-1)
        kn = k_toks.reshape(-1, width)
        vn = v_toks.reshape(-1, width)
        kf, vf = ops.kv_token_write(kf, vf, kn, vn, folded,
                                    jnp.zeros((1,), jnp.int32))
        self.k = kf.reshape(self.k.shape)
        self.v = vf.reshape(self.v.shape)

    def write_token(self, blocks: List[int], pos: int, k_tok, v_tok):
        """k_tok/v_tok: (L, Hkv, D); write at absolute position ``pos``."""
        self.write_tokens(jnp.asarray([self.slot_of(blocks, pos)], jnp.int32),
                          k_tok[:, None], v_tok[:, None])

    # ---- read path ----------------------------------------------------------
    def gather_seq(self, blocks: List[int], length: int):
        """Materialize one request's KV: (L, length, Hkv, D)."""
        idx = jnp.asarray(blocks, jnp.int32)
        shape = (self.k.shape[0], -1, self.kv_heads,
                 self.k.shape[3] // self.kv_heads)
        k = self.k[:, idx].reshape(shape)
        v = self.v[:, idx].reshape(shape)
        return k[:, :length], v[:, :length]

    def decode_attention(self, layer: int, q, block_tables, context_lens):
        """Batched paged decode attention for one layer via the Pallas kernel.

        q: (B, H, D); block_tables: (B, P) int32; context_lens: (B,).
        """
        return ops.paged_attention(q, self.k, self.v, block_tables,
                                   context_lens,
                                   jnp.asarray([layer], jnp.int32))

    # ---- copy-on-write ------------------------------------------------------
    def copy_blocks(self, src: List[int], dst: List[int]):
        """Device-local block clone (all layers, two kernel launches):
        the COW data plane — a request forking off a shared prefix block
        gets a private copy it can write into."""
        si = jnp.asarray(src, jnp.int32)
        di = jnp.asarray(dst, jnp.int32)
        self.k = ops.block_scatter_layers(
            self.k, di, ops.block_gather_layers(self.k, si))
        self.v = ops.block_scatter_layers(
            self.v, di, ops.block_gather_layers(self.v, si))

    # ---- migration (paper §6.3) ---------------------------------------------
    def offload(self, gpu_blocks: List[int], host_blocks: List[int]):
        """D2H: gather device blocks (all layers, one kernel launch) into
        staging, copy to the host pool. An ``int8_host`` tier quantizes
        inside the gather kernel (fused) so the D2H copy moves the int8
        payload + scales — half the fp16 wire bytes."""
        self._require_host("offload()")
        idx = jnp.asarray(gpu_blocks, jnp.int32)
        if self.host_precision == "int8_host":
            kq, ks = ops.block_gather_quant_layers(self.k, idx,
                                                   self.kv_heads)
            vq, vs = ops.block_gather_quant_layers(self.v, idx,
                                                   self.kv_heads)
            self.host_k[:, host_blocks] = np.asarray(kq)
            self.host_v[:, host_blocks] = np.asarray(vq)
            self.host_scales_k[:, host_blocks] = np.asarray(ks)
            self.host_scales_v[:, host_blocks] = np.asarray(vs)
            return
        self.host_k[:, host_blocks] = np.asarray(
            ops.block_gather_layers(self.k, idx))
        self.host_v[:, host_blocks] = np.asarray(
            ops.block_gather_layers(self.v, idx))

    def upload(self, host_blocks: List[int], gpu_blocks: List[int]):
        """H2D: read host blocks, scatter into (possibly new) device blocks
        across every layer in one kernel launch. An ``int8_host`` tier
        dequantizes inside the scatter kernel (fused) — the device pool
        is always full precision, so decode/prefill attention never sees
        int8 on device."""
        self._require_host("upload()")
        idx = jnp.asarray(gpu_blocks, jnp.int32)
        if self.host_precision == "int8_host":
            self.k = ops.block_scatter_dequant_layers(
                self.k, idx, jnp.asarray(self.host_k[:, host_blocks]),
                jnp.asarray(self.host_scales_k[:, host_blocks]))
            self.v = ops.block_scatter_dequant_layers(
                self.v, idx, jnp.asarray(self.host_v[:, host_blocks]),
                jnp.asarray(self.host_scales_v[:, host_blocks]))
            return
        self.k = ops.block_scatter_layers(
            self.k, idx, jnp.asarray(self.host_k[:, host_blocks]))
        self.v = ops.block_scatter_layers(
            self.v, idx, jnp.asarray(self.host_v[:, host_blocks]))
