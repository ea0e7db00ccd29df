"""Ahead-of-time compiles of the served path for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on the CPU. They catch what
interpret-mode sweeps cannot: Mosaic's block-shape and layout rules, and
programs that do not fit the chip's memory. Every compile is at
stablelm-3b's published widths. Nothing runs, so nothing here is a
result or a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import block_copy as BC
from repro.kernels import kv_write as KW
from repro.kernels import ops
from repro.kernels import paged_attention as PA
from repro.kernels import paged_prefill as PP
from repro.launch import serve
from repro.models import model as M

CFG = get_config("stablelm_3b")
L, HKV, D = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
H = CFG.num_heads
BS = 32                     # TPU_V5E.block_tokens
N = 384                     # pool pages incl. the scratch page
B, P, C, PP_ = 8, 64, 32, 3  # batch, table pages, prefill chunk, window
M_ = 16                     # blocks per migration
# memory_stats()["bytes_limit"] of one TPU v5e ("TPU v5 lite")
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The first chip of the described host, with the persistent compile
    cache off (a compile for an absent chip can be written to it but
    not read back) and no kernel trace left over from CPU tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_cases():
    bf, i32, i8, f32 = jnp.bfloat16, jnp.int32, jnp.int8, jnp.float32
    pool = ((N, BS, HKV, D), bf)
    pools = ((L, N, BS, HKV, D), bf)
    idx = ((M_,), i32)
    return {
        "paged_attention": (
            lambda q, k, v, t, c: PA.paged_attention(
                q, k, v, t, c, interpret=False, flat=False),
            [((B, H, D), bf), pool, pool, ((B, P), i32), ((B,), i32)]),
        "paged_prefill_attention": (
            lambda q, k, v, t, qp: PP.paged_prefill_attention(
                q, k, v, t, qp, interpret=False, flat=False),
            [((B, C, H, D), bf), pool, pool, ((B, P), i32), ((B, C), i32)]),
        "kv_token_write": (
            lambda k, v, kn, vn, s: KW.kv_token_write(
                k, v, kn, vn, s, interpret=False, flat=False),
            [pool, pool, ((B, HKV, D), bf), ((B, HKV, D), bf), ((B,), i32)]),
        "kv_chunk_write": (
            lambda k, v, kn, vn, wp, ws, wc: KW.kv_chunk_write(
                k, v, kn, vn, wp, ws, wc, interpret=False, flat=False),
            [pool, pool, ((B, C, HKV, D), bf), ((B, C, HKV, D), bf),
             ((B, PP_), i32), ((B,), i32), ((B,), i32)]),
        "block_gather_layers": (
            lambda p, i: BC.block_gather_layers(p, i, interpret=False),
            [pools, idx]),
        "block_scatter_layers": (
            lambda p, i, s: BC.block_scatter_layers(p, i, s,
                                                    interpret=False),
            [pools, idx, ((L, M_, BS, HKV, D), bf)]),
        "block_gather_quant_layers": (
            lambda p, i: BC.block_gather_quant_layers(p, i, interpret=False),
            [pools, idx]),
        "block_scatter_dequant_layers": (
            lambda p, i, s, sc: BC.block_scatter_dequant_layers(
                p, i, s, sc, interpret=False),
            [pools, idx, ((L, M_, BS, HKV, D), i8), ((L, M_, HKV), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases()[name]
    compiled = jax.jit(fn).lower(
        *[_shape(one_chip, s, d) for s, d in args]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_fits_v5e(one_chip, monkeypatch):
    """The whole full-width decode step compiles with Mosaic kernels, and
    its arguments plus temporaries fit one chip at the pool size the
    served engine derives for it."""
    def pool_bytes(cfg, n_blocks, block_tokens):
        shape = (cfg.num_layers, n_blocks + 1, block_tokens,
                 cfg.num_kv_heads, cfg.head_dim)
        zeros = jax.jit(lambda: jnp.zeros(shape, jnp.bfloat16),
                        out_shardings=one_chip)
        return zeros.lower().compile().memory_analysis() \
            .output_size_in_bytes

    # the compile sees the CPU as its backend: steer both the kernel mode
    # and the pool layout probe to the described chip
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(serve, "_pool_bytes", pool_bytes)
    n = serve.pool_blocks(CFG, BS, V5E_BYTES_LIMIT)
    assert n == 383         # what the served engine derives on the chip
    params = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                          M.param_specs(CFG))
    pool = _shape(one_chip, (L, n + 1, BS, HKV, D), jnp.bfloat16)
    vec = _shape(one_chip, (B,), jnp.int32)
    compiled = M.paged_decode_step.lower(
        CFG, params, pool, pool, vec, _shape(one_chip, (B, P), jnp.int32),
        vec, vec, vec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= V5E_BYTES_LIMIT
