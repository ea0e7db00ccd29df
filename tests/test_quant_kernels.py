"""Precision-tiered KV: int8 quantization kernels vs jnp oracles.

Three tiers of checking, loosest last:

  * *bit tier* — the Pallas quantize kernels must produce the exact int8
    payload + fp32 scales the jnp oracle produces (same formula, same
    rounding), flat and gridded variants alike;
  * *round-trip tier* — dequant(quant(x)) lands within scale/2 of x per
    element (uniform symmetric quantization's worst case);
  * *logits tier* — attention computed over a quantized pool (dequant
    fused into the kernel) stays within a loose tolerance of attention
    over the full-precision pool. Attention outputs are convex mixtures
    of V rows, so the per-element error bound survives the softmax —
    this is the tolerance the e2e backend test inherits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.backend import JaxBackend
from repro.core.costmodel import A100_PCIE
from repro.core.engine import EngineConfig
from repro.core.temporal import TemporalConfig
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kvcache.paged import PagedKVCache

KEY = jax.random.PRNGKey(21)


def one_layer(pages):
    """(N, bs, Hkv, D) pages as layer 0 of a served lane-dense pool, for
    the full-precision oracles, which read the served layout."""
    return pages.reshape(1, *pages.shape[:2], -1)


LAYER0 = jnp.zeros((1,), jnp.int32)

# quantized-pool attention vs full-precision attention: int8 round-trip
# error is <= scale/2 per element; softmax mixing keeps the output error
# the same order (scales here are ~4/127 for unit-normal inputs)
LOGITS_TOL = dict(atol=7e-2, rtol=7e-2)


def _blocks(key, m, bs, hkv, d, dtype=jnp.float32, scale=4.0):
    return scale * jax.random.normal(key, (m, bs, hkv, d), dtype)


# ---------------------------------------------------------------------------
# quantize / dequantize primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,bs,hkv,d", [
    (3, 8, 2, 32),
    (1, 16, 1, 64),
    (5, 8, 5, 16),     # odd head count
])
def test_kv_block_quant_matches_oracle_bitwise(m, bs, hkv, d, dtype, flat):
    from repro.kernels.kv_write import kv_block_quant
    x = _blocks(KEY, m, bs, hkv, d, dtype)
    q, s = kv_block_quant(x, interpret=True, flat=flat)
    q_ref, s_ref = R.quantize_block_ref(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=0, rtol=1e-6)


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
def test_kv_block_roundtrip_error_bounded_by_half_scale(flat):
    from repro.kernels.kv_write import kv_block_dequant, kv_block_quant
    m, bs, hkv, d = 4, 16, 2, 32
    x = _blocks(KEY, m, bs, hkv, d)
    q, s = kv_block_quant(x, interpret=True, flat=flat)
    y = kv_block_dequant(q, s, interpret=True, flat=flat)
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.asarray(s)[:, None, :, None] / 2 + 1e-7
    assert np.all(err <= bound), float((err - bound).max())


def test_dequant_respects_out_dtype():
    from repro.kernels.kv_write import kv_block_dequant, kv_block_quant
    x = _blocks(KEY, 2, 8, 2, 16, jnp.bfloat16)
    q, s = kv_block_quant(x, interpret=True)
    y = kv_block_dequant(q, s, out_dtype=jnp.bfloat16, interpret=True)
    assert y.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# fused migration kernels (quantize-on-offload / dequantize-on-upload)
# ---------------------------------------------------------------------------

def lane_bound(scales, d, eps):
    """Per-element round-trip bound of lane-dense rows: each kv head's
    scale/2 over its D lanes. scales (L, M, Hkv) -> (L, M, 1, Hkv*D)."""
    return np.repeat(np.asarray(scales), d, axis=-1)[:, :, None, :] / 2 \
        + eps


def test_block_gather_quant_layers_matches_oracle():
    nl, n, bs, hkv, d = 2, 10, 8, 2, 32
    ks = jax.random.split(KEY, 2)
    pools = jax.random.normal(ks[0], (nl, n, bs, hkv * d), jnp.float32)
    idx = jnp.asarray([7, 2, 5], jnp.int32)
    q, s = ops.block_gather_quant_layers(pools, idx, hkv)
    q_ref, s_ref = R.block_gather_quant_layers_ref(pools, idx, hkv)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=0, rtol=1e-6)


def test_block_scatter_dequant_layers_matches_oracle():
    nl, n, bs, hkv, d = 2, 10, 8, 2, 32
    ks = jax.random.split(KEY, 3)
    pools = jax.random.normal(ks[0], (nl, n, bs, hkv * d), jnp.float32)
    src = jax.random.normal(ks[1], (nl, 3, bs, hkv, d), jnp.float32)
    staging, scales = R.quantize_block_ref(src)
    staging = staging.reshape(nl, 3, bs, hkv * d)
    idx = jnp.asarray([1, 8, 4], jnp.int32)
    got = ops.block_scatter_dequant_layers(pools, idx, staging, scales)
    ref = R.block_scatter_dequant_layers_ref(pools, idx, staging, scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    # untouched blocks are bit-identical to the original pool
    untouched = [i for i in range(n) if i not in (1, 8, 4)]
    np.testing.assert_array_equal(
        np.asarray(got[:, untouched]), np.asarray(pools[:, untouched]))


def test_gather_scatter_roundtrip_within_half_scale():
    nl, n, bs, hkv, d = 2, 8, 8, 2, 16
    pools = jax.random.normal(KEY, (nl, n, bs, hkv * d), jnp.float32)
    idx = jnp.asarray([0, 3, 6], jnp.int32)
    q, s = ops.block_gather_quant_layers(pools, idx, hkv)
    back = ops.block_scatter_dequant_layers(pools, idx, q, s)
    err = np.abs(np.asarray(back[:, idx]) - np.asarray(pools[:, idx]))
    bound = lane_bound(s, d, 1e-7)
    assert np.all(err <= bound)


# ---------------------------------------------------------------------------
# dequant-fused attention (logits-tolerance tier)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("b,h,hkv,d,bs,p", [
    (1, 4, 4, 32, 8, 3),
    (3, 8, 2, 64, 16, 5),
    (2, 5, 5, 16, 8, 4),
])
def test_paged_attention_quant(b, h, hkv, d, bs, p, flat):
    from repro.kernels.paged_attention import paged_attention_quant
    n = p * b + 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n, bs, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n, bs, hkv, d), jnp.float32)
    bt = jax.random.randint(ks[3], (b, p), 0, n)
    cl = jax.random.randint(ks[4], (b,), 1, p * bs + 1)
    kq, kscale = R.quantize_block_ref(kp)
    vq, vscale = R.quantize_block_ref(vp)
    out = paged_attention_quant(q, kq, vq, kscale, vscale, bt, cl,
                                interpret=True, flat=flat)
    # exact vs the quant oracle (same dequant, same flash math) ...
    ref = R.paged_attention_quant_ref(q, kq, vq, kscale, vscale, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    # ... and within the logits tolerance of full-precision attention
    full = R.paged_attention_ref(q, one_layer(kp), one_layer(vp), bt,
                                 cl, LAYER0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               **LOGITS_TOL)


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("b,c,h,hkv,d,bs,p", [
    (1, 4, 4, 4, 32, 8, 3),
    (3, 8, 8, 2, 64, 16, 5),
    (2, 5, 5, 5, 16, 8, 4),
])
def test_paged_prefill_attention_quant(b, c, h, hkv, d, bs, p, flat):
    from repro.kernels.paged_prefill import paged_prefill_attention_quant
    n = p * b + 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, c, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n, bs, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n, bs, hkv, d), jnp.float32)
    bt = jax.random.randint(ks[3], (b, p), 0, n)
    qpos = jax.random.randint(ks[4], (b, c), -1, p * bs)
    kq, kscale = R.quantize_block_ref(kp)
    vq, vscale = R.quantize_block_ref(vp)
    out = paged_prefill_attention_quant(q, kq, vq, kscale, vscale, bt,
                                        qpos, interpret=True, flat=flat)
    ref = R.paged_prefill_attention_quant_ref(q, kq, vq, kscale, vscale,
                                              bt, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    full = R.paged_prefill_attention_ref(q, one_layer(kp),
                                         one_layer(vp), bt, qpos,
                                         LAYER0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               **LOGITS_TOL)
    dead = np.asarray(qpos) < 0
    if dead.any():
        assert np.all(np.asarray(out)[dead] == 0.0)


# ---------------------------------------------------------------------------
# PagedKVCache int8 host tier + host_blocks=0 regression
# ---------------------------------------------------------------------------

MCFG = ModelConfig(name="tiny-f32", arch_type="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=128, dtype="float32")


def test_paged_cache_int8_offload_upload_roundtrip():
    cache = PagedKVCache(MCFG, num_blocks=8, block_size=16, host_blocks=4,
                         dtype=jnp.float32, host_precision="int8_host")
    assert cache.host_k.dtype == np.int8
    assert cache.host_scales_k.shape == (2, 4, 2)
    ks = jax.random.split(KEY, 2)
    cache.k = jax.random.normal(ks[0], cache.k.shape, jnp.float32)
    cache.v = jax.random.normal(ks[1], cache.v.shape, jnp.float32)
    orig_k = np.asarray(cache.k[:, [1, 3, 5]]).copy()
    orig_v = np.asarray(cache.v[:, [1, 3, 5]]).copy()
    cache.offload([1, 3, 5], [0, 1, 2])
    # clobber the device blocks, then promote into fresh ones
    cache.k = cache.k.at[:, jnp.asarray([1, 3, 5])].set(0)
    cache.v = cache.v.at[:, jnp.asarray([1, 3, 5])].set(0)
    cache.upload([0, 1, 2], [6, 7, 0])
    back_k = np.asarray(cache.k[:, [6, 7, 0]])
    back_v = np.asarray(cache.v[:, [6, 7, 0]])
    d = MCFG.head_dim
    bound_k = lane_bound(cache.host_scales_k[:, :3], d, 1e-7)
    bound_v = lane_bound(cache.host_scales_v[:, :3], d, 1e-7)
    assert np.all(np.abs(back_k - orig_k) <= bound_k)
    assert np.all(np.abs(back_v - orig_v) <= bound_v)


def test_paged_cache_fp16_roundtrip_still_bit_exact():
    cache = PagedKVCache(MCFG, num_blocks=8, block_size=16, host_blocks=4,
                         dtype=jnp.float32)
    cache.k = jax.random.normal(KEY, cache.k.shape, jnp.float32)
    cache.v = cache.k + 1.0
    orig = np.asarray(cache.k[:, [2, 4]]).copy()
    cache.offload([2, 4], [0, 1])
    cache.k = cache.k.at[:, jnp.asarray([2, 4])].set(0)
    cache.upload([0, 1], [2, 4])
    np.testing.assert_array_equal(np.asarray(cache.k[:, [2, 4]]), orig)


def test_host_blocks_zero_allocates_nothing_and_errors_loudly():
    """Regression for the phantom host block: host_blocks=0 used to
    allocate max(n, 1) blocks — a full L*bs*Hkv*D slab nobody could ever
    legitimately address — and a misrouted offload silently 'succeeded'
    into it. Now the tier-off cache holds no host pool at all and any
    host-path call is a loud error."""
    cache = PagedKVCache(MCFG, num_blocks=4, block_size=16, host_blocks=0,
                         dtype=jnp.float32)
    assert cache.host_k is None and cache.host_v is None
    assert cache.host_scales_k is None and cache.host_scales_v is None
    with pytest.raises(RuntimeError, match="host tier is disabled"):
        cache.offload([1], [0])
    with pytest.raises(RuntimeError, match="host tier is disabled"):
        cache.upload([0], [1])


# ---------------------------------------------------------------------------
# e2e: backend decode across a quantize -> offload -> promote -> dequant
# cycle stays within the logits tolerance (greedy tokens identical)
# ---------------------------------------------------------------------------

def _mk_backend(host_precision):
    ecfg = EngineConfig(
        mode="baseline", gpu_blocks=24, host_blocks=16,
        temporal=TemporalConfig(kv_precision=host_precision))
    return JaxBackend(MCFG, ecfg, A100_PCIE)


def _mk_req(rid, prompt, blocks):
    from repro.core.graph import AppGraph
    from repro.core.request import Request
    g = AppGraph("t")
    node = g.add_agent("a", "worker", len(prompt), decode_len=64)
    r = Request(rid=rid, app_id="app", node=node, graph=g, arrival=0.0,
                prompt_tokens=list(prompt))
    r.gpu_blocks_by_device[0] = list(blocks)
    return r


def test_backend_decode_survives_int8_offload_promote_cycle():
    """Same shape as the fp16 bit-exact round-trip test, with the int8
    host tier: KV quantizes on copy_out, dequantizes on copy_in into NEW
    device blocks, and greedy decode afterwards produces exactly the
    tokens of an uninterrupted run (logits move less than the argmax
    margin at this scale) while the restored cache stays within the
    per-block quantization bound."""
    steps_before, steps_after = 4, 4
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(0, 128, 20)]

    ref_backend = _mk_backend("int8_host")
    ref = _mk_req("r", prompt, blocks=[1, 2, 3])
    for _ in range(steps_before + steps_after):
        ref_backend.decode([ref])

    backend = _mk_backend("int8_host")
    assert backend.cache.host_precision == "int8_host"
    r = _mk_req("r", prompt, blocks=[1, 2, 3])
    for _ in range(steps_before):
        backend.decode([r])
    snap_k = np.asarray(backend.cache.k[:, jnp.asarray([1, 2, 3])]).copy()
    r.host_blocks = [0, 1, 2]
    backend.copy_out(r)
    assert backend.cache.host_k.dtype == np.int8
    backend.cache.k = backend.cache.k.at[:, jnp.asarray([1, 2, 3])].set(0)
    backend.cache.v = backend.cache.v.at[:, jnp.asarray([1, 2, 3])].set(0)
    r.reserved_upload_blocks = [10, 11, 12]
    backend.copy_in(r)
    r.gpu_blocks_by_device[0] = [10, 11, 12]
    r.reserved_upload_blocks = []
    back_k = np.asarray(backend.cache.k[:, jnp.asarray([10, 11, 12])])
    bound = lane_bound(backend.cache.host_scales_k[:, :3], MCFG.head_dim,
                       1e-6)
    assert np.all(np.abs(back_k - snap_k) <= bound)
    for _ in range(steps_after):
        backend.decode([r])
    assert backend.generated["r"] == ref_backend.generated["r"]
