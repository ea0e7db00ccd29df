"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

The served pool is every layer's pages, (L, N, bs, Hkv*D) with each token
row lane-dense, and the decode/prefill kernels take it whole with a layer
index. The sweeps use a layer other than 0 of a three-layer pool, so a
kernel that reads or writes the wrong layer fails, and the write kernels
must leave every other layer bit-identical."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(7)


NL = 3                      # layers of the swept pools
LAYER = jnp.asarray([1], jnp.int32)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


def pools(key, n, bs, hkv, d, dtype):
    """A K and a V pool of NL layers in the served lane-dense layout."""
    kk, kv = jax.random.split(key)
    return (jax.random.normal(kk, (NL, n, bs, hkv * d), dtype),
            jax.random.normal(kv, (NL, n, bs, hkv * d), dtype))


def assert_other_layers_intact(out, before):
    keep = [i for i in range(NL) if i != int(LAYER[0])]
    np.testing.assert_array_equal(np.asarray(out)[keep],
                                  np.asarray(before)[keep])


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,d,bs,p", [
    (1, 4, 4, 32, 8, 3),      # MHA
    (3, 8, 2, 64, 16, 5),     # GQA 4:1
    (2, 16, 1, 64, 32, 2),    # MQA
    (2, 5, 5, 16, 8, 4),      # odd head count (whisper-like)
    (2, 10, 2, 80, 8, 3),     # GQA 5:1 at stablelm-3b's head size
    (2, 10, 2, 128, 16, 3),   # GQA 5:1 at Qwen2.5-14B's head size
])
def test_paged_attention(dtype, b, h, hkv, d, bs, p, flat):
    from repro.kernels.paged_attention import paged_attention
    n = p * b + 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kp, vp = pools(ks[1], n, bs, hkv, d, dtype)
    bt = jax.random.randint(ks[3], (b, p), 0, n)
    cl = jax.random.randint(ks[4], (b,), 1, p * bs + 1)
    out = paged_attention(q, kp, vp, bt, cl, LAYER, interpret=True,
                          flat=flat)
    ref = R.paged_attention_ref(q, kp, vp, bt, cl, LAYER)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,h,hkv,d,bs,p", [
    (1, 4, 4, 4, 32, 8, 3),    # MHA, small chunk
    (3, 8, 8, 2, 64, 16, 5),   # GQA 4:1
    (2, 16, 16, 1, 64, 32, 2),  # MQA, chunk spans whole pages
    (2, 5, 5, 5, 16, 8, 4),    # odd chunk + odd head count
    (2, 4, 10, 2, 80, 8, 3),   # GQA 5:1 at stablelm-3b's head size
    (1, 8, 10, 2, 128, 16, 3),  # GQA 5:1 at Qwen2.5-14B's head size
])
def test_paged_prefill_attention(dtype, b, c, h, hkv, d, bs, p, flat):
    """Chunked suffix-prefill attention vs the dense oracle, including
    causal masking against arbitrary absolute positions and fully-masked
    padded queries (q_pos = -1 -> zero rows, not NaN)."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    n = p * b + 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, c, h, d), dtype)
    kp, vp = pools(ks[1], n, bs, hkv, d, dtype)
    bt = jax.random.randint(ks[3], (b, p), 0, n)
    qpos = jax.random.randint(ks[4], (b, c), -1, p * bs)
    out = paged_prefill_attention(q, kp, vp, bt, qpos, LAYER,
                                  interpret=True, flat=flat)
    ref = R.paged_prefill_attention_ref(q, kp, vp, bt, qpos, LAYER)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))
    # padded queries produce exact zeros
    dead = np.asarray(qpos) < 0
    if dead.any():
        got = np.asarray(out, np.float32)
        assert np.all(got[dead] == 0.0)


def test_paged_prefill_matches_decode_convention():
    """A 1-token chunk at position ctx equals paged *decode* attention with
    context ctx+1 — the suffix-prefill and decode paths agree at the seam."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.paged_prefill import paged_prefill_attention
    b, h, hkv, d, bs, p = 2, 4, 2, 32, 8, 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kp, vp = pools(ks[1], p * b + 2, bs, hkv, d, jnp.float32)
    bt = jax.random.randint(ks[3], (b, p), 0, p * b + 2)
    ctx = jnp.asarray([5, 17], jnp.int32)
    pre = paged_prefill_attention(q, kp, vp, bt, ctx[:, None], LAYER,
                                  interpret=True)
    dec = paged_attention(q[:, 0], kp, vp, bt, ctx + 1, LAYER,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(pre[:, 0]), np.asarray(dec),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [1, 4, 7])
def test_block_gather_scatter(dtype, m):
    pages = jax.random.normal(KEY, (12, 8, 2, 16), dtype)
    idx = jnp.asarray(np.random.default_rng(0).choice(12, m, replace=False),
                      jnp.int32)
    stg = ops.block_gather(pages, idx)
    np.testing.assert_array_equal(np.asarray(stg),
                                  np.asarray(R.block_gather_ref(pages, idx)))
    new = jax.random.normal(jax.random.PRNGKey(9), (m, 8, 2, 16), dtype)
    out = ops.block_scatter(pages, idx, new)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(R.block_scatter_ref(pages, idx, new)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [1, 3, 5])
def test_block_gather_scatter_layers(dtype, m):
    """All-layer migration kernels match the per-layer refs, on the
    served pool's lane-dense pages."""
    kp, _ = pools(KEY, 10, 8, 2, 16, dtype)
    idx = jnp.asarray(np.random.default_rng(1).choice(10, m, replace=False),
                      jnp.int32)
    stg = ops.block_gather_layers(kp, idx)
    np.testing.assert_array_equal(
        np.asarray(stg), np.asarray(R.block_gather_layers_ref(kp, idx)))
    new = jax.random.normal(jax.random.PRNGKey(2), (NL, m, 8, 32), dtype)
    out = ops.block_scatter_layers(kp, idx, new)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(R.block_scatter_layers_ref(kp, idx, new)))


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b", [1, 4, 7])
@pytest.mark.parametrize("hkv,d", [(2, 16), (2, 80), (1, 128)])
def test_kv_token_write(dtype, b, flat, hkv, d):
    """Batched decode-token scatter matches the functional ref, in the
    written layer only."""
    from repro.kernels.kv_write import kv_token_write
    n, bs = 12, 8
    ks = jax.random.split(KEY, 4)
    kp, vp = pools(ks[0], n, bs, hkv, d, dtype)
    kn = jax.random.normal(ks[2], (b, hkv, d), dtype)
    vn = jax.random.normal(ks[3], (b, hkv, d), dtype)
    rng = np.random.default_rng(4)
    blocks = rng.choice(n, b, replace=False)        # distinct blocks
    offs = rng.integers(0, bs, b)
    slots = jnp.asarray(blocks * bs + offs, jnp.int32)
    ko, vo = kv_token_write(kp, vp, kn, vn, slots, LAYER, interpret=True,
                            flat=flat)
    kr, vr = R.kv_token_write_ref(kp, vp, kn, vn, slots, LAYER)
    np.testing.assert_array_equal(np.asarray(ko), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vr))
    assert_other_layers_intact(ko, kp)
    assert_other_layers_intact(vo, vp)


@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat(cpu)", "grid(tpu)"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,start", [(1, 4, 2), (3, 8, 0), (2, 6, 7),
                                       (2, 8, 3)])
@pytest.mark.parametrize("hkv,d", [(2, 16), (2, 80), (1, 128)])
def test_kv_chunk_write(dtype, b, c, start, flat, hkv, d):
    """Suffix-chunk scatter (prefill write path) matches the functional
    ref: windows starting mid-page, spilling across page boundaries, with
    per-row valid counts and padded rows (wcount=0) never writing. The
    gridded variant owns one destination page per step, so live pages are
    never revisited (the TPU aliasing hazard a per-token grid would have)."""
    from repro.kernels.kv_write import kv_chunk_write
    n, bs = 12, 8
    pp = (c - 1) // bs + 2
    ks = jax.random.split(KEY, 4)
    kp, vp = pools(ks[0], n, bs, hkv, d, dtype)
    kn = jax.random.normal(ks[2], (b, c, hkv, d), dtype)
    vn = jax.random.normal(ks[3], (b, c, hkv, d), dtype)
    rng = np.random.default_rng(6)
    # each row gets its own disjoint pages; last row only partially valid
    wpages = np.full((b, pp), n - 1, np.int32)          # scratch = page n-1
    wcount = np.full((b,), c, np.int32)
    wcount[-1] = max(c - 2, 1)
    free = list(rng.permutation(n - 1))
    for i in range(b):
        npages = (start + int(wcount[i]) + bs - 1) // bs
        wpages[i, :npages] = [free.pop() for _ in range(npages)]
    wstart = np.full((b,), start, np.int32)
    ko, vo = kv_chunk_write(kp, vp, kn, vn, jnp.asarray(wpages),
                            jnp.asarray(wstart), jnp.asarray(wcount), LAYER,
                            interpret=True, flat=flat)
    kr, vr = R.kv_chunk_write_ref(kp, vp, kn, vn, jnp.asarray(wpages),
                                  jnp.asarray(wstart), jnp.asarray(wcount),
                                  LAYER)
    # scratch (page n-1) holds dead content and the variants differ there
    # by design (scatter vs skip); every live page must match exactly
    np.testing.assert_array_equal(np.asarray(ko)[:, :-1],
                                  np.asarray(kr)[:, :-1])
    np.testing.assert_array_equal(np.asarray(vo)[:, :-1],
                                  np.asarray(vr)[:, :-1])
    # untouched pages (outside every window, except scratch) are intact,
    # and so is every other layer, scratch included
    touched = set(wpages.reshape(-1).tolist()) | {n - 1}
    keep = np.array([p for p in range(n) if p not in touched], int)
    if keep.size:
        np.testing.assert_array_equal(np.asarray(ko)[:, keep],
                                      np.asarray(kp)[:, keep])
    assert_other_layers_intact(ko, kp)
    assert_other_layers_intact(vo, vp)


def test_kv_token_write_scratch_collisions_leave_live_blocks_alone():
    """Masked rows all share one scratch block; live blocks stay intact."""
    n, bs, hkv, d = 6, 4, 2, 8
    ks = jax.random.split(KEY, 4)
    kp, vp = pools(ks[0], n, bs, hkv, d, jnp.float32)
    kn = jax.random.normal(ks[2], (3, hkv, d), jnp.float32)
    vn = jax.random.normal(ks[3], (3, hkv, d), jnp.float32)
    scratch = (n - 1) * bs                          # block 5 = scratch
    slots = jnp.asarray([2 * bs + 1, scratch, scratch], jnp.int32)
    ko, vo = ops.kv_token_write(kp, vp, kn, vn, slots, LAYER)
    ko, vo, kp, vp = (np.asarray(x)[1] for x in (ko, vo, kp, vp))
    # the live write landed
    np.testing.assert_array_equal(ko[2, 1], np.asarray(kn[0]).reshape(-1))
    # every block except the written one and scratch is untouched
    keep = np.array([0, 1, 3, 4])
    np.testing.assert_array_equal(ko[keep], kp[keep])
    np.testing.assert_array_equal(vo[keep], vp[keep])
    np.testing.assert_array_equal(ko[2, 0], kp[2, 0])
    np.testing.assert_array_equal(ko[2, 2:], kp[2, 2:])


def test_migration_roundtrip_bit_exact():
    """Offload then upload restores the pool exactly (paper §6.3)."""
    pages = jax.random.normal(KEY, (16, 8, 2, 16), jnp.bfloat16)
    idx = jnp.array([2, 5, 9], jnp.int32)
    staged = ops.block_gather(pages, idx)
    wiped = ops.block_scatter(pages, idx, jnp.zeros_like(staged))
    restored = ops.block_scatter(wiped, idx, staged)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(pages))


@pytest.mark.parametrize("s,q", [(64, 16), (128, 64), (96, 32)])
@pytest.mark.parametrize("h,p,n", [(2, 8, 4), (3, 16, 8)])
def test_ssd_scan(s, q, h, p, n):
    ks = jax.random.split(KEY, 5)
    B = 2
    x = jax.random.normal(ks[0], (B, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    b = jax.random.normal(ks[3], (B, s, n))
    c = jax.random.normal(ks[4], (B, s, n))
    y, st = ops.ssd_scan(x, dt, dt * A, b, c, chunk=q)
    yr, sr = R.ssd_scan_ref(x, dt, dt * A, b, c)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(st, sr, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,w,qb", [(128, 32, 64), (256, 96, 64),
                                    (128, 128, 128)])
def test_swa_attention(dtype, s, w, qb):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, s, 3, 32), dtype)
    k = jax.random.normal(ks[1], (2, s, 3, 32), dtype)
    v = jax.random.normal(ks[2], (2, s, 3, 32), dtype)
    out = ops.swa_attention(q, k, v, w, q_block=qb, kv_block=qb)
    ref = R.swa_attention_ref(q, k, v, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_model_chunk_scan_matches_sequential():
    """The pure-jnp chunked SSD in the model matches the recurrence."""
    from repro.configs.base import ModelConfig
    from repro.models.ssm import _ssd_chunk_scan
    cfg = ModelConfig(name="t", arch_type="ssm", num_layers=1, d_model=64,
                      num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=16,
                      ssm_state=8, ssm_head_dim=16, ssm_chunk=32)
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 100, 2, 16, 8   # S deliberately not chunk-aligned
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N))
    c = jax.random.normal(ks[4], (B, S, N))
    y, st = _ssd_chunk_scan(cfg, x, dt, dt * A, b, c)
    yr, sr = R.ssd_scan_ref(x, dt, dt * A, b, c)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(st, sr, atol=2e-3, rtol=2e-3)
