#!/usr/bin/env python3
"""Chip smoke: serve stablelm-3b at its published widths on one TPU.

    python chip_smoke.py [--seed N]

One process drives the served path once, through the entry points a user
calls: the asyncio HTTP front door, the engine with both schedulers,
``JaxBackend``, the paged KV cache with real host migration, and the
compiled Pallas kernels. Weights are drawn from ``--seed``.

Phases (any failure exits non-zero and prints no result):

  a. device and config; the compiled decode step holds Mosaic kernels
     (``tpu_custom_call``);
  b. ``/generate`` requests with a shared long prefix (one streamed) and
     one registered app whose tool call stalls long enough for the Time
     Scheduler to offload and upload it;
  c. the paged prefill's last-position logits against ``M.prefill`` on
     the same weights;
  d. blocks offloaded to the host tier and uploaded into other blocks
     come back bit-identical;
  e. peak device memory, pool sizes, wall times, requests served.

Wall times here are the client's and the host's clocks around calls that
end in a host read; the server's ``ttft``/``latency`` fields are the
engine's virtual seconds. Neither is a device metric.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
import traceback
from types import SimpleNamespace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "stablelm_3b"


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phases (also run in-process at smoke width by tests/test_chip_smoke.py)
# ---------------------------------------------------------------------------

def build(cfg, seed: int):
    """The served engine: ``serve.build_model_engine`` with a Temporal
    Scheduler that offloads any stalled request whose blocks a queued
    request could use (as ``examples/serve_multiagent.py`` does)."""
    from repro.core.temporal import TemporalConfig
    from repro.launch.serve import build_model_engine

    return build_model_engine(
        cfg, seed=seed, continuous_batching=True,
        temporal=TemporalConfig(score_threshold=-1.0,
                                pressure_watermark=0.0))


def decode_step_hlo(eng) -> str:
    """Compiled HLO of the full-width decode step at batch 1."""
    from repro.models import model as M

    be = eng.backend
    one = jnp.zeros((1,), jnp.int32)
    table = jnp.zeros((1, 16), jnp.int32)
    return M.paged_decode_step.lower(
        be.cfg, be.params, be.cache.k, be.cache.v, one, table, one, one,
        one).compile().as_text()


def _prompts(cfg, seed: int, prefix_len: int, lens) -> list:
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).tolist()
    return [prefix + rng.integers(0, cfg.vocab_size,
                                  n - prefix_len).tolist() for n in lens]


def _post(port: int, path: str, obj: dict, timeout: float):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("POST", path, json.dumps(obj),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, r.read().decode()
    finally:
        c.close()


def _get(port: int, path: str, timeout: float) -> dict:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("GET", path)
        r = c.getresponse()
        check(r.status == 200, f"GET {path} -> {r.status}")
        return json.loads(r.read())
    finally:
        c.close()


def _wait(port: int, what: str, cond, timeout: float) -> dict:
    """Poll ``/v1/report`` until ``cond(report)`` holds."""
    deadline = time.monotonic() + timeout
    while True:
        rep = _get(port, "/v1/report", 60)
        if cond(rep):
            return rep
        check(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.05)


def phase_serve(eng, seed: int, prefix_len: int = 512,
                lens=(1024, 1536, 1984, 1280), max_tokens: int = 32,
                stall_s: float = 30.0, graph_prompt: int = 1984,
                timeout: float = 900.0) -> dict:
    """Boot the HTTP front door on ``eng`` and serve one registered app
    whose first agent decodes, stalls ``stall_s`` virtual seconds in a
    tool call, and decodes again before its successor runs; and, sent
    with it, concurrent ``/generate`` requests with prompt lengths
    cycling through ``lens`` (all sharing a ``prefix_len``-token prefix,
    the last one streamed), as many as it takes to need more blocks than
    the device pool holds.
    Requests then queue for the agent's blocks when it stalls, which is
    what the Time Scheduler offloads for. Checks every status, every
    decoded token against the backend's own output, and the migration
    counters."""
    from repro.launch.http_server import HttpServer

    cfg = eng.backend.cfg
    bt = eng.platform.block_tokens
    sizes = []
    while sum(-(-(n + max_tokens) // bt) for n in sizes) \
            <= eng.cfg.gpu_blocks:
        sizes.append(lens[len(sizes) % len(lens)])
    prompts = _prompts(cfg, seed, prefix_len, sizes)
    srv = HttpServer(engine=eng, cache_enabled=False)
    port = srv.start_background()
    results = [None] * len(prompts)
    walls = [None] * len(prompts)

    def client(i: int) -> None:
        stream = i == len(prompts) - 1
        t0 = time.perf_counter()
        results[i] = _post(port, "/generate" + ("?stream=1" if stream
                                                else ""),
                           {"prompt": prompts[i], "max_tokens": max_tokens},
                           timeout)
        walls[i] = time.perf_counter() - t0

    try:
        # everything arrives in one engine step. The agent heads a
        # two-node DAG, so the Spatial Scheduler ranks it above the
        # single-node /generate apps: it is admitted first, the requests
        # fill the pool, the rest queue, and the agent stalls while they
        # still wait
        srv.pause()
        spec = {"name": "stall", "nodes": [
            {"name": "agent", "agent_type": "tool_user",
             "prompt_len": graph_prompt, "decode_segments": [8, 8],
             "func_calls": [{"name": "search", "tool": "search",
                             "predict_time": stall_s}]},
            {"name": "writer", "agent_type": "writer", "prompt_len": 64,
             "decode_len": 8, "deps": ["agent"]}]}
        status, body = _post(port, "/v1/register_graph", {"graph": spec},
                             timeout)
        check(status == 200, f"register_graph -> {status}: {body}")
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        _wait(port, "requests accepted",
              lambda r: r["serving"]["accepted"] == len(prompts), timeout)
        srv.resume()
        for t in threads:
            t.join(timeout)
        rep = _wait(port, "apps finished",
                    lambda r: r["apps_finished"] == len(prompts) + 1,
                    timeout)
    finally:
        srv.stop()

    for i, (status, body) in enumerate(results):
        check(status == 200, f"/generate {i} -> {status}: {body[:300]}")
        if i == len(prompts) - 1:           # streamed: reassemble chunks
            msgs = [json.loads(ln) for ln in body.splitlines()]
            toks = [t for m in msgs for t in m["tokens"]]
            check(msgs[-1]["done"] and msgs[-1]["n_tokens"] == len(toks),
                  "stream did not end with a done chunk")
        else:
            out = json.loads(body)
            toks, rid = out["tokens"], out["rid"]
            check(toks == eng.backend.generated[rid][:len(toks)],
                  f"/generate {i} tokens are not the backend's")
        check(len(toks) == max_tokens,
              f"/generate {i} decoded {len(toks)} of {max_tokens} tokens")
    check(rep["offloads"] >= 1, f"offloads {rep['offloads']}")
    check(rep["uploads"] >= 1, f"uploads {rep['uploads']}")
    check(rep["truncated_prompt_tokens"] == 0,
          f"truncated_prompt_tokens {rep['truncated_prompt_tokens']}")
    return {"requests_served": len(prompts) + 1,
            "prompt_tokens": [len(p) for p in prompts],
            "client_wall_s": walls,
            "offloads": rep["offloads"], "uploads": rep["uploads"],
            "decoded_tokens": rep["decoded_tokens"],
            "truncated_prompt_tokens": rep["truncated_prompt_tokens"]}


# The served path and the reference run the same bf16 weights, and both
# keep the residual stream in bf16. They differ in how f32 sums are
# ordered (32-token chunks and an online softmax over 32-token pages
# against one softmax over the whole sequence) and so in which values
# round to bf16 where. One bf16 rounding is a relative error of up to
# 2**-8 of the value; a few such roundings per layer, compounded over the
# depth, stay within a few percent of the largest logit. Hence the bound
# below, relative to the reference's largest |logit|; the argmax must
# agree exactly.
LOGIT_RTOL = 0.05


def phase_logits(eng, seed: int, n_tokens: int = 1024):
    """Prefill one prompt through the backend's paged path (the engine's
    ``decode`` hook, into blocks taken from the engine's pool) and compare
    its last-position logits with ``M.prefill`` on the same weights.
    Returns (report, blocks holding the prompt's KV) for phase d."""
    from repro.models import model as M

    be, cfg = eng.backend, eng.backend.cfg
    prompt = _prompts(cfg, seed + 1, 0, [n_tokens])[0]
    bs = be.block_tokens
    blocks = eng.pools[0].allocate(-(-(n_tokens + 1) // bs), "smoke")
    req = SimpleNamespace(rid="smoke/logits", gpu_blocks=blocks,
                          num_gpu_blocks=len(blocks), prompt_tokens=prompt)
    be.decode([req])
    paged = be.last_prefill_logits[req.rid]
    be.invalidate(req.rid)
    be.generated.pop(req.rid)
    ref, _ = jax.jit(M.prefill, static_argnums=0)(
        cfg, be.params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    ref = np.asarray(ref[0, -1], np.float32)
    err = float(np.max(np.abs(paged - ref)))
    scale = float(np.max(np.abs(ref)))
    out = {"prompt_tokens": n_tokens, "max_abs_err": err,
           "ref_max_abs": scale, "rtol": LOGIT_RTOL,
           "top1_paged": int(np.argmax(paged)),
           "top1_ref": int(np.argmax(ref))}
    check(np.all(np.isfinite(paged)), "paged logits not finite")
    check(err <= LOGIT_RTOL * scale, f"logits differ: {out}")
    check(out["top1_paged"] == out["top1_ref"], f"top-1 differs: {out}")
    return out, blocks


def phase_migration(eng, src: list) -> dict:
    """Offload ``src`` (blocks holding real KV) to the host tier, upload
    it into freshly allocated blocks, and compare every layer's K and V
    bit for bit."""
    from repro.kernels import ops

    cache = eng.backend.cache
    dst = eng.pools[0].allocate(len(src), "smoke")
    host = eng.host.allocate(len(src), "smoke")
    si, di = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    before = [np.asarray(ops.block_gather_layers(p, si))
              for p in (cache.k, cache.v)]
    t0 = time.perf_counter()
    cache.offload(src, host)
    cache.upload(host, dst)
    after = [np.asarray(ops.block_gather_layers(p, di))
             for p in (cache.k, cache.v)]
    wall = time.perf_counter() - t0
    eng.host.release(host)
    eng.pools[0].release(dst)
    eng.pools[0].release(src)
    for name, b, a in zip("kv", before, after):
        check(np.any(b != 0), f"{name}: source blocks hold no KV")
        check(np.array_equal(b.view(np.uint8), a.view(np.uint8)),
              f"{name}: uploaded blocks differ from the offloaded ones")
    return {"blocks": len(src), "bytes_each_way": sum(b.nbytes
                                                      for b in before),
            "bit_exact": True, "round_trip_wall_s": wall}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2

    from repro.configs.base import get_config
    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cfg = get_config(ARCH)
    walls = {}
    phase = "a"
    try:
        say(f"[a] device {json.dumps(device)}")
        say(f"[a] config {cfg.name}: {cfg.num_layers} layers, d_model "
            f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} kv, "
            f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}, {cfg.dtype}, weights from seed {args.seed}")
        t0 = time.perf_counter()
        eng = build(cfg, args.seed)
        walls["build_s"] = time.perf_counter() - t0
        be = eng.backend
        say(f"[a] pool: gpu_blocks {eng.cfg.gpu_blocks}, host_blocks "
            f"{eng.cfg.host_blocks}, block_tokens {be.block_tokens}, "
            f"device pool bytes {be.cache.k.nbytes + be.cache.v.nbytes}")
        t0 = time.perf_counter()
        hlo = decode_step_hlo(eng)
        walls["decode_step_compile_s"] = time.perf_counter() - t0
        n_kern = hlo.count("tpu_custom_call")
        check(n_kern > 0, "decode step holds no tpu_custom_call")
        say(f"[a] decode step compiled in "
            f"{walls['decode_step_compile_s']:.3f}s: {n_kern} "
            "tpu_custom_call sites  PASS")

        phase = "b"
        t0 = time.perf_counter()
        served = phase_serve(eng, args.seed)
        walls["serve_s"] = time.perf_counter() - t0
        say(f"[b] HTTP serve {json.dumps(served)}  PASS")

        phase = "c"
        t0 = time.perf_counter()
        logits, blocks = phase_logits(eng, args.seed)
        walls["logits_s"] = time.perf_counter() - t0
        say(f"[c] logits vs M.prefill {json.dumps(logits)}  PASS")

        phase = "d"
        t0 = time.perf_counter()
        mig = phase_migration(eng, blocks)
        walls["migration_s"] = time.perf_counter() - t0
        say(f"[d] migration {json.dumps(mig)}  PASS")

        phase = "e"
        stats = dev.memory_stats()
        say(f"[e] peak_bytes_in_use {stats['peak_bytes_in_use']} of "
            f"bytes_limit {stats['bytes_limit']}; gpu_blocks "
            f"{eng.cfg.gpu_blocks}; requests served "
            f"{served['requests_served']}; host wall times "
            f"{json.dumps(walls)}")
    except Exception:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        print(f"chip_smoke: phase {phase} FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
