"""Public jit'd wrappers for the Pallas kernels.

The kernel mode follows the backend the call is traced for: on a TPU
every kernel compiles to Mosaic (gridded variants); on the CPU it runs in
Pallas interpret mode (flat variants), which is how the tests validate
the kernels against the ``ref`` oracles. Any other backend is refused —
there is no fallback path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_copy as _bc
from repro.kernels import kv_write as _kw
from repro.kernels import paged_attention as _pa
from repro.kernels import paged_prefill as _pp
from repro.kernels import ssd_scan as _ssd
from repro.kernels import swa_attention as _swa


def _interpret() -> bool:
    """Pallas interpret mode for the current backend, decided when a
    wrapper is traced (never at import)."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


@functools.partial(jax.jit, static_argnames=())
def paged_attention(q, k_pages, v_pages, block_tables, context_lens):
    """Decode attention over the paged KV pool. See kernel docstring."""
    return _pa.paged_attention(q, k_pages, v_pages, block_tables,
                               context_lens, interpret=_interpret())


@jax.jit
def paged_prefill_attention(q, k_pages, v_pages, block_tables, q_pos):
    """Chunked suffix-prefill attention over the paged KV pool."""
    return _pp.paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                       q_pos, interpret=_interpret())


@jax.jit
def block_gather(pages, indices):
    """Gather pool blocks into a contiguous staging buffer (offload)."""
    return _bc.block_gather(pages, indices, interpret=_interpret())


@jax.jit
def block_scatter(pages, indices, staging):
    """Scatter a staging buffer into pool blocks (upload), in place."""
    return _bc.block_scatter(pages, indices, staging, interpret=_interpret())


@jax.jit
def block_gather_layers(pools, indices):
    """Gather blocks across every layer at once (offload staging)."""
    return _bc.block_gather_layers(pools, indices, interpret=_interpret())


@jax.jit
def block_scatter_layers(pools, indices, staging):
    """Scatter a staging buffer into pool blocks across every layer."""
    return _bc.block_scatter_layers(pools, indices, staging,
                                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=())
def paged_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                          block_tables, context_lens):
    """Decode attention over an int8-quantized pool (dequant fused)."""
    return _pa.paged_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                                     block_tables, context_lens,
                                     interpret=_interpret())


@jax.jit
def paged_prefill_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                                  block_tables, q_pos):
    """Chunked suffix-prefill attention over an int8-quantized pool."""
    return _pp.paged_prefill_attention_quant(q, k_pages, v_pages, k_scale,
                                             v_scale, block_tables, q_pos,
                                             interpret=_interpret())


@jax.jit
def kv_block_quant(blocks):
    """Quantize staged KV blocks to int8 + per-(block, kv-head) scales."""
    return _kw.kv_block_quant(blocks, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def kv_block_dequant(q, scales, out_dtype=jnp.float32):
    """Dequantize int8 KV blocks back to ``out_dtype``."""
    return _kw.kv_block_dequant(q, scales, out_dtype, interpret=_interpret())


@jax.jit
def block_gather_quant_layers(pools, indices):
    """Fused all-layer gather + int8 quantize (quantize-on-offload)."""
    return _bc.block_gather_quant_layers(pools, indices,
                                         interpret=_interpret())


@jax.jit
def block_scatter_dequant_layers(pools, indices, staging, scales):
    """Fused dequantize + all-layer scatter (promotion/pull delivery)."""
    return _bc.block_scatter_dequant_layers(pools, indices, staging,
                                            scales, interpret=_interpret())


@jax.jit
def kv_token_write(k_pages, v_pages, k_new, v_new, slots):
    """Batched one-token-per-sequence KV write into the paged pool."""
    return _kw.kv_token_write(k_pages, v_pages, k_new, v_new, slots,
                              interpret=_interpret())


@jax.jit
def kv_chunk_write(k_pages, v_pages, k_new, v_new, wpages, wstart, wcount):
    """Suffix-chunk KV scatter (prefill write path). Gridded per
    destination page on TPU — a chunk lands several tokens in the same
    page, so a per-token grid would revisit aliased output pages across
    steps; here each live page is one grid step. Flat one-shot scatter
    under the CPU interpreter."""
    return _kw.kv_chunk_write(k_pages, v_pages, k_new, v_new, wpages,
                              wstart, wcount, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a, b, c, chunk: int = 64):
    """Chunked Mamba2 SSD scan; returns (y, final_state)."""
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "q_block", "kv_block"))
def swa_attention(q, k, v, window: int, q_block: int = 128,
                  kv_block: int = 128):
    """Sliding-window causal flash attention (prefill)."""
    return _swa.swa_attention(q, k, v, window, q_block, kv_block,
                              interpret=_interpret())
