"""The program's own record of the served path (``repro.obs``): span
nesting, the bounded deque and its dropped count, compiles counted under
the open span, and the wall-clock stamps of a streamed request through a
real HTTP server on the JAX backend."""
import concurrent.futures as cf
import http.client
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.backend import JaxBackend
from repro.core.costmodel import A100_PCIE
from repro.core.engine import Engine, EngineConfig
from repro.launch.http_server import HttpServer


@pytest.fixture(autouse=True)
def fresh_record():
    obs.reset()
    yield
    obs.reset()


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r[0], []).append(r)
    return out


def test_spans_nest_and_name_their_parent():
    with obs.span("engine.step"):
        with obs.span("backend.decode") as sp:
            obs.event("backend.first_token", rid="a/r")
            sp.set(rows=3)
        obs.event("engine.kv_used", value=0.5)
    recs = _by_name(obs.records())
    (step,), (dec,) = recs["engine.step"], recs["backend.decode"]
    assert step[3] is None and dec[3] == "engine.step"
    assert step[1] <= dec[1] <= dec[2] <= step[2]
    assert dec[5] == {"rows": 3}
    (tok,) = recs["backend.first_token"]
    assert tok[3] == "backend.decode" and tok[4] == "a/r"
    assert tok[1] == tok[2]
    (kv,) = recs["engine.kv_used"]
    assert kv[3] == "engine.step" and kv[5] == {"value": 0.5}
    # the stack is empty again: a new span has no parent
    with obs.span("frontdoor.poll"):
        pass
    assert obs.records()[-1][3] is None


def test_span_ends_on_exception_and_keeps_the_stack():
    with pytest.raises(ValueError):
        with obs.span("engine.step"):
            with obs.span("engine.schedule"):
                raise ValueError("boom")
    with obs.span("frontdoor.poll"):
        pass
    names = [(r[0], r[3]) for r in obs.records()]
    assert names == [("engine.schedule", "engine.step"),
                     ("engine.step", None), ("frontdoor.poll", None)]


@pytest.mark.parametrize("maxlen", [4, 16])
def test_deque_bound_and_dropped_count(maxlen):
    obs.reset(maxlen=maxlen)
    for i in range(maxlen):
        obs.event("engine.kv_used", value=i)
    assert obs.dropped() == 0
    t_mid = obs.records()[maxlen // 2][1]
    assert len(obs.records(since=t_mid)) == maxlen - maxlen // 2
    for i in range(maxlen // 2 + 1):
        obs.event("engine.kv_used", value=maxlen + i)
    st = obs.stats()
    assert st["size"] == maxlen and st["held"] == maxlen
    assert st["dropped"] == maxlen // 2 + 1
    # the stretch from t_mid lost its first record: no answer, not a
    # short one; a stretch that starts after the loss is still whole
    assert obs.records(since=t_mid) is None
    assert obs.records() is None
    held = obs.records(since=obs._held()[0][0][2])
    assert held is not None and len(held) == maxlen


def test_counters_are_flat():
    obs.count("frontdoor.bytes", 10)
    obs.count("frontdoor.bytes")
    assert obs.stats()["counters"]["frontdoor.bytes"] == 11


def test_compile_counted_under_the_open_span():
    # a constant unique to this run keeps the program out of any
    # persistent compile cache
    c = float(int.from_bytes(os.urandom(4), "little"))
    fn = jax.jit(lambda x: x * c + 1.0)
    with obs.span("backend.decode_step"):
        fn(jnp.ones((3, 5), jnp.float32)).block_until_ready()
    assert obs.stats()["compiles"].get("backend.decode_step", 0) >= 1


def _tiny_server():
    cfg = ModelConfig(name="tiny-f32", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    ecfg = EngineConfig.preset("tokencake", gpu_blocks=96, host_blocks=64,
                               max_running=8, sched_quantum=4,
                               continuous_batching=True)
    eng = Engine(ecfg, A100_PCIE, backend=JaxBackend(cfg, ecfg, A100_PCIE))
    return HttpServer(engine=eng, cache_enabled=False)


def _stream(port, prompt, n):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    c.request("POST", "/generate?stream=1",
              json.dumps({"prompt": prompt, "max_tokens": n}),
              {"Content-Type": "application/json"})
    r = c.getresponse()
    lines = [json.loads(ln) for ln in r.read().decode().splitlines()]
    c.close()
    return lines


def test_http_round_trip_stamps_each_request_in_order():
    srv = _tiny_server()
    port = srv.start_background()
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, 128, 20 + 6 * i)]
               for i in range(4)]
    try:
        with cf.ThreadPoolExecutor(4) as ex:
            outs = list(ex.map(lambda p: _stream(port, p, 6), prompts))
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        c.request("GET", "/v1/report")
        rep = json.loads(c.getresponse().read())
        c.close()
    finally:
        srv.stop()
    assert all(o[-1]["done"] and o[-1]["n_tokens"] == 6 for o in outs)
    recs = obs.records()
    recv = {r[5]["gid"]: r[1] for r in recs if r[0] == "frontdoor.recv"}
    rid_of = {r[5]["gid"]: r[4] for r in recs
              if r[0] == "frontdoor.submit"}
    assert set(recv) == set(rid_of) and len(recv) == len(prompts)

    def first(name, rid):
        return min(r[1] for r in recs if r[0] == name and r[4] == rid)
    for gid, rid in rid_of.items():
        assert rid
        admit = first("engine.admit", rid)
        tok = first("backend.first_token", rid)
        chunk = first("frontdoor.first_chunk", rid)
        assert recv[gid] <= admit <= tok <= chunk, (gid, rid)
    wall = rep["serving"]["wall"]
    for key in ("queue_wait", "ttft", "first_chunk_lag"):
        assert wall[key]["n"] == len(prompts)
        assert 0 <= wall[key]["p50"] <= wall[key]["p99"]
    tr = rep["tracing"]
    assert tr["size"] == obs.MAXLEN and tr["dropped"] == 0
    assert tr["held"] > 0 and isinstance(tr["compiles"], dict)
    names = {r[0] for r in recs}
    assert {"engine.step", "engine.events", "engine.schedule",
            "engine.prefix_match", "engine.kv_used", "backend.decode",
            "backend.prefill", "backend.prefill_chunk",
            "backend.decode_step", "backend.decode_prep",
            "backend.decode_sync", "frontdoor.poll", "frontdoor.stream",
            "frontdoor.park"} <= names
    assert all(n.startswith(("frontdoor.", "engine.", "backend."))
               for n in names)
