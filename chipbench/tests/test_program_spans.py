"""The per-layer metrics that read the program's own record
(``repro.obs``): a traced run of each cell at the smoke size reports
each of them with a value, and each reader answers nothing for a window
the record no longer holds whole."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import smoke
from repro import obs
from repro.launch import serve

ROOT = Path(__file__).resolve().parents[2]
# the metrics that read the record, by cell
NEW = {"stablelm-3b.chat": ["queue_wait_p50_s", "prefill_wall_p50_s",
                            "first_chunk_lag_p50_ms"],
       "stablelm-3b.chat-over": ["decode_host_ms_per_launch",
                                 "prefill_stall_ms_per_step",
                                 "decode_rows_mean", "kv_used_share"]}
READERS = [m for ms in NEW.values() for m in ms]


def _reader(name):
    return run._module(run.BENCH / "metrics" / f"{name}.py")


def test_the_entries_name_the_cells_they_read():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    got = {m["name"]: m["workloads"] for m in spec["per_layer"]
           if m["name"] in READERS}
    assert got == {m: [cell] for cell, ms in NEW.items() for m in ms}


@pytest.mark.parametrize("index", [0, 1])
def test_traced_run_reports_each_new_metric(index, monkeypatch):
    # the CPU reports no memory limit: stand in a budget of a few dozen
    # KV blocks at the smoke width
    monkeypatch.setattr(serve, "hbm_limit", lambda: 4 << 20)
    obs.reset()
    c = smoke.cell(index)
    out = run.run(c, 2**31 + 77 + index, 5.0, True, time.monotonic())
    assert out["correct"], out["compared"]
    for name in NEW[c.name]:
        assert out["metrics"][name]["value"] is not None, name
        assert out["metrics"][name]["value"] >= 0, name
    assert obs.dropped() == 0


def _flow(rid, gid, t_step=0.002):
    """One request through the front door, engine and backend, with an
    engine step around its decode launch."""
    obs.event("frontdoor.recv", gid=gid)
    with obs.span("frontdoor.submit", gid=gid) as sp:
        sp.rid = rid
    with obs.span("engine.step"):
        obs.event("engine.admit", rid=rid, cached=0)
        with obs.span("backend.decode"):
            with obs.span("backend.prefill", rows=1, tokens=[(0, 8)]):
                time.sleep(t_step)
            with obs.span("backend.decode_step"):
                with obs.span("backend.decode_prep", rows=1, pages=1,
                              keys=[9]):
                    pass
                with obs.span("backend.decode_sync"):
                    time.sleep(t_step)
                obs.event("backend.first_token", rid=rid)
        obs.event("engine.kv_used", value=0.25)
    with obs.span("frontdoor.stream", rid=rid):
        obs.event("frontdoor.first_chunk", rid=rid, gid=gid)


def _window(maxlen, n=3):
    obs.reset(maxlen=maxlen)
    obs.event("engine.kv_used", value=0.5)
    ws = time.monotonic()
    for i in range(n):
        _flow(f"gen#{i}/r", f"g{i}")
    return SimpleNamespace(window=(ws, time.monotonic() + 1e-3))


def test_readers_answer_on_a_whole_record():
    ctx = _window(1 << 10)
    for name in READERS:
        assert _reader(name).read(ctx) is not None, name
    assert _reader("decode_rows_mean").read(ctx) == 1.0
    assert 25.0 <= _reader("kv_used_share").read(ctx) <= 50.0
    obs.reset()


@pytest.mark.parametrize("name", READERS)
def test_reader_refuses_a_window_the_record_dropped(name):
    ctx = _window(20)
    assert obs.dropped() > 0
    assert _reader(name).read(ctx) is None
    obs.reset()
