"""Ahead-of-time compiles of the served path for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on the CPU. They catch what
interpret-mode sweeps cannot: Mosaic's block-shape and layout rules, and
programs that do not fit the chip's memory. Every compile is at
stablelm-3b's published widths, and the step programs also at one
pipeline stage of Qwen2.5-14B (12 of 48 layers, every width as
published). Nothing runs, so nothing here is a result or a time.
"""
import dataclasses
import os
import re

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
# the step programs at every width they are served at
STEP_CFGS = {
    "stablelm-3b": CFG,
    "qwen2.5-14b-stage": dataclasses.replace(
        get_config("qwen2_5_14b_serving"), num_layers=12),
}
POOL_BLOCKS = 383           # what serve.pool_blocks derives on a v5e
STEP_TEMP_LIMIT = 256 << 20


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
    pools = ((L, N, BS, HKV * D), bf)
    layer = ((1,), i32)
    idx = ((M_,), i32)
    return {
        "paged_attention": (
            lambda q, k, v, t, c, ly: PA.paged_attention(
                q, k, v, t, c, ly, interpret=False, flat=False),
            [((B, H, D), bf), pools, pools, ((B, P), i32), ((B,), i32),
             layer]),
        "paged_prefill_attention": (
            lambda q, k, v, t, qp, ly: PP.paged_prefill_attention(
                q, k, v, t, qp, ly, interpret=False, flat=False),
            [((B, C, H, D), bf), pools, pools, ((B, P), i32),
             ((B, C), i32), layer]),
        "kv_token_write": (
            lambda k, v, kn, vn, s, ly: KW.kv_token_write(
                k, v, kn, vn, s, ly, interpret=False, flat=False),
            [pools, pools, ((B, HKV, D), bf), ((B, HKV, D), bf),
             ((B,), i32), layer]),
        "kv_chunk_write": (
            lambda k, v, kn, vn, wp, ws, wc, ly: KW.kv_chunk_write(
                k, v, kn, vn, wp, ws, wc, ly, interpret=False, flat=False),
            [pools, pools, ((B, C, HKV, D), bf), ((B, C, HKV, D), bf),
             ((B, PP_), i32), ((B,), i32), ((B,), i32), layer]),
        "block_gather_layers": (
            lambda p, i: BC.block_gather_layers(p, i, interpret=False),
            [pools, idx]),
        "block_scatter_layers": (
            lambda p, i, s: BC.block_scatter_layers(p, i, s,
                                                    interpret=False),
            [pools, idx, ((L, M_, BS, HKV * D), bf)]),
        "block_gather_quant_layers": (
            lambda p, i: BC.block_gather_quant_layers(p, i, HKV,
                                                      interpret=False),
            [pools, idx]),
        "block_scatter_dequant_layers": (
            lambda p, i, s, sc: BC.block_scatter_dequant_layers(
                p, i, s, sc, interpret=False),
            [pools, idx, ((L, M_, BS, HKV * D), i8), ((L, M_, HKV), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases()[name]
    compiled = jax.jit(fn).lower(
        *[_shape(one_chip, s, d) for s, d in args]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pool(one_chip, cfg):
    return _shape(one_chip, (cfg.num_layers, POOL_BLOCKS + 1, BS,
                             cfg.num_kv_heads * cfg.head_dim), jnp.bfloat16)


@pytest.fixture(scope="module")
def step_program(one_chip):
    """Compiled step programs, ``(config name, "decode" | "prefill")`` ->
    the compiled program, each compiled once for the module: the decode
    step at batch 16 over 40 table pages, the prefill step at one row of a
    32-token chunk, both on the 383-block pool."""
    done = {}

    def compiled(name, step):
        if (name, step) not in done:
            cfg = STEP_CFGS[name]
            params = jax.tree.map(
                lambda s: _shape(one_chip, s.shape, s.dtype),
                M.param_specs(cfg))
            pool = _pool(one_chip, cfg)

            def i32(*shape):
                return _shape(one_chip, shape, jnp.int32)
            # the compile sees the CPU as its backend: steer the kernel
            # mode to the described chip
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(ops, "_interpret", lambda: False)
                if step == "decode":
                    low = M.paged_decode_step.lower(
                        cfg, params, pool, pool, i32(16), i32(16, 40),
                        i32(16), i32(16), i32(16))
                else:
                    low = M.paged_prefill_step.lower(
                        cfg, params, pool, pool, i32(1, C), i32(1, 32),
                        i32(1, C), i32(1, PP_), i32(1), i32(1))
                done[name, step] = low.compile()
        return done[name, step]
    return compiled


def test_decode_step_fits_v5e(one_chip, step_program, monkeypatch):
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

    # the pool sizing probe sees the CPU too: steer it to the chip
    monkeypatch.setattr(serve, "_pool_bytes", pool_bytes)
    n = serve.pool_blocks(CFG, BS, V5E_BYTES_LIMIT)
    assert n == POOL_BLOCKS  # what the served engine derives on the chip
    compiled = step_program("stablelm-3b", "decode")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= V5E_BYTES_LIMIT


# an HLO instruction: its name, result type and opcode
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+(.+?)\s+"
                          r"([a-z][\w-]*)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "fusion")


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("name", sorted(STEP_CFGS))
def test_step_reads_pool_in_place(step_program, name, step):
    """The step programs hand the kernels the whole pool and never move
    it: no copy, dynamic-slice, dynamic-update-slice or fusion yields a
    layer's pool or more (a result with the pool's page axes), and the
    temporaries stay far below one pool (they held two per-layer
    relayouts and a restacked pool, 4.35 GB at stablelm-3b, when the
    pools rode the layer scan as inputs and outputs). The kernels keep
    the names the device trace is read by."""
    compiled = step_program(name, step)
    text = compiled.as_text()
    pages = re.compile(rf"\[(\d+,)*{POOL_BLOCKS + 1},{BS}[,\]]")
    moved = [line.strip()[:160] for line in text.splitlines()
             if (m := _INSTRUCTION.match(line)) and m.group(3) in _MOVES
             and pages.search(m.group(2))]
    assert not moved, "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < STEP_TEMP_LIMIT
    kernel = "paged_attention" if step == "decode" \
        else "paged_prefill_attention"
    assert re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(", text)
