"""Plain reference of the served dense decoder, in float32.

A pre-norm decoder: RMSNorm with a (1 + gain) scale, grouped-query
attention with rotary position embedding (rotate-half convention, over
``partial_rotary_factor`` of each head), optional biases on q, k and v,
a SwiGLU feed-forward, a final RMSNorm and an untied output head. It is
written from that description in plain ``jax.numpy``: no kernel, no
paging, no batching; matrix products at ``Precision.HIGHEST``. The
configuration file holds the published keys and, under ``assumed``, the
values the served block runs where it departs from them; this reference
follows what is run.

The weights are drawn from the run's seed by the same recipe the served
model's are (normal draws scaled by the fan-in, in bfloat16; zero gains
and biases), here; nothing is taken from the program.

``logits_at`` runs a token sequence through the model in chunks of rows
against a float32 KV cache, and returns, at the positions asked for, the
largest logit and the logits of the tokens asked for. With
``fp8=True`` every projection and the output head take their operands
rounded to float8 (e4m3, scaled per row and per output column) and the KV
cache is rounded the same way: the same model one precision step below
the configuration's bfloat16, which serves as the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
CHUNK = 256                      # rows per pass
E4M3_MAX = 448.0


def dims(conf: dict) -> dict:
    conf = {**conf, **conf.get("assumed", {})}
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dict(L=conf["num_hidden_layers"], d=d, H=h,
                Hkv=conf["num_key_value_heads"], Dh=d // h,
                F=conf["intermediate_size"], V=conf["vocab_size"],
                theta=float(conf["rope_theta"]),
                rot=float(conf["partial_rotary_factor"]),
                eps=float(conf["rms_norm_eps"]),
                bias=bool(conf["attention_bias"]))


def weights(conf: dict, seed: int) -> dict:
    """bfloat16 weights from ``seed``: the served model's draws, made op
    by op as the served model makes them, so that every value is the
    same bit for bit (one fused program rounds a few in a million
    differently)."""
    m = dims(conf)
    L, d, H, Hkv, Dh, F, V = (m[k] for k in ("L", "d", "H", "Hkv", "Dh",
                                              "F", "V"))

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)
    zeros = lambda *s: jnp.zeros(s, jnp.bfloat16)  # noqa: E731
    k_embed, k_head, k_layers, _ = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    k_attn, k_mlp, *_ = jax.random.split(k_layers, 6)
    ka = jax.random.split(k_attn, 8)
    km = jax.random.split(k_mlp, 3)
    w = {
        "embed": normal(k_embed, (V, d), 0.02),
        "head": normal(k_head, (d, V), 1 / math.sqrt(d)),
        "final_norm": zeros(d),
        "wq": normal(ka[0], (L, d, H * Dh), 1 / math.sqrt(d)),
        "wk": normal(ka[1], (L, d, Hkv * Dh), 1 / math.sqrt(d)),
        "wv": normal(ka[2], (L, d, Hkv * Dh), 1 / math.sqrt(d)),
        "wo": normal(ka[3], (L, H * Dh, d), 1 / math.sqrt(H * Dh * L)),
        "attn_norm": zeros(L, d), "mlp_norm": zeros(L, d),
        "w1": normal(km[0], (L, d, F), 1 / math.sqrt(d)),
        "w2": normal(km[1], (L, F, d), 1 / math.sqrt(F * L)),
        "w3": normal(km[2], (L, d, F), 1 / math.sqrt(d)),
    }
    if m["bias"]:
        w.update(bq=zeros(L, H * Dh), bk=zeros(L, Hkv * Dh),
                 bv=zeros(L, Hkv * Dh))
    return w


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta, rot):
    dh = x.shape[-1]
    r = int(round(dh * rot)) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos[:, None].astype(jnp.float32) * inv          # (C, r/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            rest], -1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _chunk(m, fp8, w, tokens, start, kc, vc, targets):
    """Rows ``start .. start+CHUNK`` of a sequence: extend the caches
    ``kc``/``vc`` (L, S, Hkv, Dh) and return, per row, the largest logit
    and the logits of ``targets`` (C, T) (-1: none)."""
    md = dict(m)
    H, Hkv, Dh = md["H"], md["Hkv"], md["Dh"]
    C = tokens.shape[0]
    pos = start + jnp.arange(C)
    S = kc.shape[1]
    kpos = jnp.arange(S)
    mask = kpos[None, :] <= pos[:, None]                   # (C, S)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        kl, vl = lw["k"], lw["v"]
        h = _rms(x, lw["attn_norm"], md["eps"])
        q, k, v = (_mm(h, lw[n], fp8) for n in ("wq", "wk", "wv"))
        if md["bias"]:
            q, k, v = (t + lw[b].astype(jnp.float32)
                       for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
        q = _rope(q.reshape(C, H, Dh), pos, md["theta"], md["rot"])
        k = _rope(k.reshape(C, Hkv, Dh), pos, md["theta"], md["rot"])
        v = v.reshape(C, Hkv, Dh)
        if fp8:
            k, v = _fp8(k, -1), _fp8(v, -1)
        kl = jax.lax.dynamic_update_slice(kl, k, (start, 0, 0))
        vl = jax.lax.dynamic_update_slice(vl, v, (start, 0, 0))
        qg = q.reshape(C, Hkv, H // Hkv, Dh)
        s = jnp.einsum("cgjd,sgd->cgjs", qg, kl, precision=HI) \
            / math.sqrt(Dh)
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("cgjs,sgd->cgjd", p, vl, precision=HI)
        x = x + _mm(o.reshape(C, H * Dh), lw["wo"], fp8)
        h = _rms(x, lw["mlp_norm"], md["eps"])
        f = jax.nn.silu(_mm(h, lw["w1"], fp8)) * _mm(h, lw["w3"], fp8)
        x = x + _mm(f, lw["w2"], fp8)
        return x, (kl, vl)

    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "head", "final_norm")}
    per_layer.update(k=kc, v=vc)
    x, (kc, vc) = jax.lax.scan(layer, x, per_layer)
    logits = _mm(_rms(x, w["final_norm"], md["eps"]), w["head"], fp8)
    top = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0), -1)
    return top, jnp.where(targets >= 0, got, jnp.nan), \
        jnp.argmax(logits, -1), kc, vc


def new_cache(conf: dict, tokens: int):
    """Empty float32 KV caches holding ``tokens`` positions (rounded up
    to whole chunks)."""
    m = dims(conf)
    S = -(-tokens // CHUNK) * CHUNK
    z = jnp.zeros((m["L"], S, m["Hkv"], m["Dh"]), jnp.float32)
    return z, z


def logits_at(conf: dict, w: dict, seq, start: int, cache, targets,
              fp8: bool = False):
    """Run ``seq[start:]`` (whose first ``start`` positions are already
    in ``cache``, a multiple of ``CHUNK``) and return, for each position
    ``i >= start``: the largest logit, the logits of ``targets[i]`` (a
    list of token ids, possibly empty) and the argmax; and the cache."""
    m = tuple(sorted(dims(conf).items()))
    kc, vc = cache
    width = max((len(t) for t in targets), default=0) or 1
    tops, vals, arg = [], [], []
    for c0 in range(start, len(seq), CHUNK):
        n = min(CHUNK, len(seq) - c0)
        toks = np.zeros(CHUNK, np.int32)
        toks[:n] = seq[c0:c0 + n]
        tg = np.full((CHUNK, width), -1, np.int32)
        for i in range(n):
            row = targets[c0 + i]
            tg[i, :len(row)] = row
        top, got, am, kc, vc = _chunk(m, fp8, w, jnp.asarray(toks),
                                      jnp.int32(c0), kc, vc,
                                      jnp.asarray(tg))
        tops.append(np.asarray(top)[:n])
        vals.append(np.asarray(got)[:n])
        arg.append(np.asarray(am)[:n])
    return (np.concatenate(tops), np.concatenate(vals),
            np.concatenate(arg), (kc, vc))
