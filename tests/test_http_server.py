"""Serving front door: response cache, admission control, HTTP surface,
and continuous-batching equivalence.

Covers the ISSUE-9 acceptance points:
  * endpoint round-trips over a real socket (stdlib client only);
  * streaming chunk reassembly equals the non-streamed result;
  * a response-cache hit serves with ZERO engine work (no new app, no
    decoded token);
  * TTL expiry turns a stale hit back into a miss;
  * backpressure: a flooded accept queue rejects with the structured
    429 shape (PR 6 error schema);
  * continuous batching is output-equivalent to per-quantum batching on
    a fixed trace (token-identical under the real JAX backend).
"""
import http.client
import json
import time

import pytest

from repro.configs.base import ModelConfig
from repro.core.costmodel import A100_PCIE
from repro.core.engine import Engine, EngineConfig
from repro.launch.http_server import FrontDoor, HttpServer, synth_tokens
from repro.launch.response_cache import ResponseCache, request_key


def mk_front(continuous=True, cache=True, ttl=1e9, max_pending=64,
             **engine_kw):
    kw = dict(gpu_blocks=256, max_running=32, sched_quantum=8,
              continuous_batching=continuous)
    kw.update(engine_kw)
    eng = Engine(EngineConfig.preset("tokencake", **kw), A100_PCIE)
    rc = ResponseCache(ttl=ttl, clock=lambda: eng.clock) if cache else None
    return FrontDoor(eng, cache=rc, max_pending=max_pending)


PROMPT = synth_tokens("prompt/a", 48)
PROMPT_B = synth_tokens("prompt/b", 48)


# ---------------------------------------------------------------- cache unit

def test_request_key_canonical():
    a = {"prompt": [1, 2, 3], "max_tokens": 8}
    b = {"max_tokens": 8, "prompt": [1, 2, 3]}        # key order irrelevant
    assert request_key(a) == request_key(b)
    assert request_key(a) != request_key({"prompt": [1, 2, 4],
                                          "max_tokens": 8})
    assert request_key(a) != request_key({"prompt": [1, 2, 3],
                                          "max_tokens": 9})


def test_cache_ttl_and_lru():
    now = [0.0]
    c = ResponseCache(ttl=10.0, max_entries=2, clock=lambda: now[0])
    c.put("k1", {"v": 1})
    assert c.get("k1") == {"v": 1}
    now[0] = 11.0
    assert c.get("k1") is None                         # lazy TTL expiry
    assert c.metrics["expirations"] == 1
    c.put("k1", {"v": 1})
    c.put("k2", {"v": 2})
    c.get("k1")                                        # k1 now MRU
    c.put("k3", {"v": 3})                              # evicts LRU = k2
    assert c.get("k2") is None
    assert c.get("k1") is not None
    assert c.metrics["evictions"] == 1
    assert c.flush() == 2
    assert len(c) == 0 and c.metrics["cached_bytes"] == 0


def test_ttl_without_clock_rejected():
    # a TTL on the default constant clock would never expire anything:
    # the constructor refuses the silent footgun outright
    with pytest.raises(ValueError):
        ResponseCache(ttl=5.0)
    ResponseCache(ttl=None)                            # no TTL: no clock ok


def test_cache_report_shape():
    c = ResponseCache(ttl=5.0, clock=lambda: 0.0)
    c.put("k", {"v": 1})
    c.get("k")
    c.get("missing")
    rep = c.report()
    assert rep["hits"] == 1 and rep["misses"] == 1
    assert rep["hit_rate"] == 0.5 and rep["entries"] == 1
    assert rep["hit_bytes"] > 0 and rep["cached_bytes"] > 0


# ------------------------------------------------------------ front door sim

def test_cache_hit_zero_engine_work():
    fd = mk_front()
    fd.submit({"prompt": PROMPT, "max_tokens": 8})
    fd.drive()
    decoded = fd.engine.metrics["decoded_tokens"]
    n_apps = len(fd.engine.apps)
    first = next(iter(fd.gens.values()))
    gen = fd.submit({"prompt": PROMPT, "max_tokens": 8})
    assert gen.status == "cached"
    # hits carry no TTFT/TPOT sample (docs/SERVING_API.md semantics);
    # end-to-end latency still counts the (instant) hit
    assert gen.ttft() is None and gen.tpot() is None
    assert gen.latency() == 0.0
    assert gen.result["tokens"] == first.result["tokens"]
    # the hit never touched the engine: no app, no decode step
    assert len(fd.engine.apps) == n_apps
    assert fd.engine.metrics["decoded_tokens"] == decoded
    assert fd.cache.metrics["hits"] == 1


def test_cache_ttl_expiry_recomputes():
    fd = mk_front(ttl=0.5)
    fd.submit({"prompt": PROMPT, "max_tokens": 8})
    fd.drive()
    decoded = fd.engine.metrics["decoded_tokens"]
    # within TTL on the virtual clock: hit
    assert fd.submit({"prompt": PROMPT, "max_tokens": 8}).status == "cached"
    # past TTL: miss -> the engine decodes again
    fd.submit({"prompt": PROMPT, "max_tokens": 8},
              arrival=fd.engine.clock + 1.0)
    fd.drive()
    assert fd.cache.metrics["expirations"] >= 1
    assert fd.engine.metrics["decoded_tokens"] > decoded
    assert all(g.done for g in fd.gens.values())


def test_backpressure_structured_rejection():
    fd = mk_front(cache=False, max_pending=4)
    for i in range(10):    # simultaneous burst >> accept bound
        fd.submit({"prompt": synth_tokens(f"bp/{i}", 32), "max_tokens": 4})
    rejected = [g for g in fd.gens.values() if g.status == "rejected"]
    assert len(rejected) == 6 and fd.metrics["rejected"] == 6
    err = rejected[0].result
    # PR 6 structured error schema + 429 marker
    assert err["ok"] is False and err["op"] == "generate"
    assert err["status"] == 429 and "backpressure" in err["error"]
    assert err["queue_depth"] >= 4
    fd.drive()
    assert fd.metrics["completed"] == 4


def test_trace_arrivals_respect_bound_as_queue_drains():
    # arrivals spread over time: later ones are admitted once earlier
    # ones finish — the bound is on concurrency, not on trace length
    fd = mk_front(cache=False, max_pending=8)
    for i in range(24):
        fd.submit({"prompt": synth_tokens(f"q/{i}", 32), "max_tokens": 4},
                  arrival=0.2 * i)
    rep = fd.drive()
    assert rep["completed"] == 24 and rep["rejected"] == 0


def test_report_distributions():
    fd = mk_front()
    for i in range(6):
        fd.submit({"prompt": synth_tokens(f"d/{i % 2}", 32),
                   "max_tokens": 8}, arrival=0.5 * i)
    rep = fd.drive()
    assert rep["completed"] + rep["cache_hits"] == 6
    for k in ("ttft", "tpot", "latency"):
        d = rep[k]
        assert d["n"] > 0 and d["p50"] <= d["p99"]
    assert rep["qps_sustained"] > 0
    assert rep["response_cache"]["hits"] == rep["cache_hits"]


def test_bad_payload_rejected():
    fd = mk_front()
    with pytest.raises(ValueError):
        fd.submit({"prompt": [], "max_tokens": 8})
    with pytest.raises(ValueError):
        fd.submit({"prompt": ["x"], "max_tokens": 8})
    with pytest.raises(ValueError):
        fd.submit({"prompt": [1, 2], "max_tokens": 0})


# -------------------------------------------------------------- HTTP socket

@pytest.fixture(scope="module")
def server():
    srv = HttpServer(engine_kw=dict(gpu_blocks=256), cache_ttl=1e9,
                     max_pending=8)
    port = srv.start_background()
    yield srv, port
    srv.stop()


def _req(port, method, path, body=None):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    c.request(method, path,
              json.dumps(body) if body is not None else None,
              {"Content-Type": "application/json"})
    r = c.getresponse()
    raw = r.read()
    c.close()
    return r.status, json.loads(raw)


def _drain(srv, port, timeout=60.0):
    """Wait (wall clock) until the server has no outstanding work."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _, rep = _req(port, "GET", "/v1/report")
        if rep["serving"]["outstanding"] == 0:
            return rep
        time.sleep(0.02)
    raise AssertionError("server did not drain")


def test_http_health_and_404(server):
    srv, port = server
    status, out = _req(port, "GET", "/healthz")
    assert status == 200 and out["ok"] is True and "clock" in out
    status, out = _req(port, "GET", "/no/such/route")
    assert status == 404 and out["ok"] is False
    status, out = _req(port, "POST", "/generate", None)
    assert status == 400   # missing prompt
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    c.request("POST", "/v1/call_start", b"{not json", {})
    assert c.getresponse().status == 400
    c.close()


def test_http_generate_roundtrip_and_cache(server):
    srv, port = server
    body = {"prompt": PROMPT, "max_tokens": 6}
    status, out = _req(port, "POST", "/generate", body)
    assert status == 200 and out["ok"] is True
    assert len(out["tokens"]) == out["n_tokens"] > 0
    assert out["cached"] is False and out["ttft"] >= 0.0
    _drain(srv, port)
    decoded = srv.engine.metrics["decoded_tokens"]
    n_apps = len(srv.engine.apps)
    status, hit = _req(port, "POST", "/generate", body)
    assert status == 200 and hit["cached"] is True
    assert hit["tokens"] == out["tokens"] and hit["ttft"] == 0.0
    # zero engine work for the hit: no new app, no decoded token
    assert srv.engine.metrics["decoded_tokens"] == decoded
    assert len(srv.engine.apps) == n_apps


def test_http_streaming_reassembles(server):
    srv, port = server
    body = {"prompt": PROMPT_B, "max_tokens": 6}
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    c.request("POST", "/generate?stream=1", json.dumps(body),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    assert r.status == 200
    lines = [json.loads(ln) for ln in r.read().decode().splitlines()]
    c.close()
    assert lines[-1]["done"] is True
    streamed = [t for msg in lines for t in msg["tokens"]]
    assert len(streamed) == lines[-1]["n_tokens"] == 6
    # chunks reassemble to exactly the non-streamed (now cached) result
    _, flat = _req(port, "POST", "/generate", body)
    assert flat["tokens"] == streamed


def test_http_async_and_result_poll(server):
    srv, port = server
    body = {"prompt": synth_tokens("async/x", 40), "max_tokens": 5}
    status, out = _req(port, "POST", "/generate?async=1", body)
    assert status == 200 and out["status"] in ("queued", "running")
    gid = out["id"]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        status, res = _req(port, "GET", f"/v1/result/{gid}")
        assert status == 200
        if res.get("status") == "finished":
            assert len(res["tokens"]) == 5
            break
        time.sleep(0.02)
    else:
        raise AssertionError("async generation never finished")
    status, _ = _req(port, "GET", "/v1/result/nope")
    assert status == 404


def test_http_register_graph_and_states(server):
    srv, port = server
    spec = {"name": "wf", "nodes": [
        {"name": "plan", "agent_type": "planner", "prompt_len": 32,
         "decode_len": 4},
        {"name": "act", "agent_type": "actor", "prompt_len": 32,
         "decode_len": 4, "deps": ["plan"],
         "func_calls": [{"name": "s", "tool": "search",
                         "predict_time": 0.05}]},
    ]}
    status, out = _req(port, "POST", "/v1/register_graph", {"graph": spec})
    assert status == 200 and out["ok"] and out["app_id"].startswith("wf#")
    status, out = _req(port, "POST", "/v1/register_graph",
                       {"graph": {"nodes": [{"bad": 1}]}})
    assert status == 400
    rep = _drain(srv, port)
    assert rep["apps_finished"] >= 1
    status, states = _req(port, "GET", "/v1/states")
    assert status == 200
    # a bad-rid call round-trips the PR 6 error schema over the wire
    status, err = _req(port, "POST", "/v1/call_start", {"rid": "bogus"})
    assert status == 400 and err == {"ok": False, "op": "call_start",
                                     "rid": "bogus",
                                     "error": "unknown rid"}


def test_http_backpressure_429(server):
    srv, port = server
    _drain(srv, port)
    srv.pause()          # freeze the pump: nothing drains the queue
    try:
        time.sleep(0.05)
        outs = []
        for i in range(12):    # max_pending=8 -> 4 structured rejections
            outs.append(_req(port, "POST", "/generate?async=1",
                             {"prompt": synth_tokens(f"flood/{i}", 32),
                              "max_tokens": 4}))
        codes = [s for s, _ in outs]
        assert codes.count(200) == 8 and codes.count(429) == 4
        rej = next(o for s, o in outs if s == 429)
        assert rej["ok"] is False and rej["op"] == "generate"
        assert "backpressure" in rej["error"] and rej["queue_depth"] == 8
    finally:
        srv.resume()
    rep = _drain(srv, port)
    assert rep["serving"]["rejected"] >= 4


def test_http_cache_flush(server):
    srv, port = server
    _drain(srv, port)
    status, out = _req(port, "POST", "/v1/cache/flush")
    assert status == 200 and out["flushed"] >= 0
    assert len(srv.front.cache) == 0


# ------------------------------------------------------------ pump failure

class _EngineFault(RuntimeError):
    pass


def _failing_step():
    raise _EngineFault("kernel refused")


@pytest.mark.parametrize("stream", [False, True])
def test_pump_failure_reaches_clients_and_owner(stream):
    """An engine step that raises stops the server: the waiting client
    gets a 500 (or a final error chunk) at once instead of its socket
    timeout, and ``stop`` re-raises the exception in the owner's
    thread."""
    srv = HttpServer(engine_kw=dict(gpu_blocks=64))
    idle_step = srv.engine.step

    def step():             # fails once the request reaches the engine
        return _failing_step() if srv.engine.apps else idle_step()

    srv.engine.step = step
    port = srv.start_background()
    t0 = time.monotonic()
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("POST", "/generate" + ("?stream=1" if stream else ""),
              json.dumps({"prompt": PROMPT, "max_tokens": 4}),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    body = r.read().decode()
    c.close()
    assert time.monotonic() - t0 < 10
    if stream:
        last = json.loads(body.splitlines()[-1])
        assert last["ok"] is False and "kernel refused" in last["error"]
    else:
        assert r.status == 500 and "kernel refused" in json.loads(body)[
            "error"]
    with pytest.raises(_EngineFault):
        srv.stop()


def test_pump_failure_ends_serve_forever():
    import asyncio

    srv = HttpServer(engine_kw=dict(gpu_blocks=64))
    srv.engine.step = _failing_step
    with pytest.raises(_EngineFault):
        asyncio.run(asyncio.wait_for(srv.serve_forever(), timeout=30))


# ----------------------------------------- continuous batching equivalence

def _sim_trace(continuous):
    fd = mk_front(continuous=continuous, cache=False)
    for i in range(12):
        fd.submit({"prompt": synth_tokens(f"eq/{i % 4}", 48),
                   "max_tokens": 16}, arrival=0.07 * i)
    rep = fd.drive()
    return fd, rep


def test_sim_equivalence_work_totals():
    """Same trace, same totals: continuous batching changes *when*
    requests join the batch, not how much work exists."""
    _, a = _sim_trace(False)
    _, b = _sim_trace(True)
    assert a["completed"] == b["completed"] == 12
    ea, eb = (_sim_trace(False)[0].engine, _sim_trace(True)[0].engine)
    assert ea.metrics["decoded_tokens"] == eb.metrics["decoded_tokens"]
    assert ea.metrics["prefill_tokens"] == eb.metrics["prefill_tokens"]


def test_continuous_equals_quantum_tokens_jax():
    """Acceptance: the same fixed trace produces token-identical outputs
    under per-quantum and token-level admission (greedy decode rows are
    independent, so batch composition must not change any sequence)."""
    from repro.core.backend import JaxBackend
    cfg = ModelConfig(name="tiny-f32", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    import numpy as np
    rng = np.random.default_rng(11)
    trace = [(0.05 * i, [int(t) for t in rng.integers(0, 128, 24 + 4 * i)])
             for i in range(4)]

    def run(continuous):
        ecfg = EngineConfig.preset(
            "tokencake", gpu_blocks=96, host_blocks=64, max_running=8,
            sched_quantum=8, continuous_batching=continuous)
        backend = JaxBackend(cfg, ecfg, A100_PCIE)
        eng = Engine(ecfg, A100_PCIE, backend=backend)
        fd = FrontDoor(eng, cache=None, max_pending=16)
        for t, prompt in trace:
            fd.submit({"prompt": prompt, "max_tokens": 8}, arrival=t)
        rep = fd.drive()
        assert rep["completed"] == len(trace)
        return {g.rid: backend.generated[g.rid] for g in fd.gens.values()}

    quantum, continuous = run(False), run(True)
    assert set(quantum) == set(continuous)
    for rid in quantum:
        assert quantum[rid] == continuous[rid], rid
        assert len(quantum[rid]) > 0
