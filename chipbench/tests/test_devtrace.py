"""The trace reduction: on hand-made records, and on a small trace
recorded on a TPU v5e (``data/trace_v5e.json``: the backend's decode
and block-copy hooks under the harness's probes, reduced to records by
``devtrace.load``)."""
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"
CHIP = "/device:TPU:0"


def test_busy_idle_ops_and_gaps_by_host_span():
    rec = {
        "modules": [[CHIP, "jit_step", 100, 200], [CHIP, "jit_step", 250,
                                                    100],
                    [CHIP, "jit_head", 600, 100]],
        # a while holding two kernels; one op outside any program
        "ops": [[CHIP, "while", 100, 180], [CHIP, "paged_attention", 110,
                                            50],
                [CHIP, "copy", 170, 60], [CHIP, "fusion", 600, 100]],
        "host": [["python", "engine.step", 0, 1000],
                 ["python", "backend.decode", 90, 300],
                 ["python", "frontdoor.poll", 400, 150]],
    }
    r = devtrace.reduce(rec, 1000)
    # busy: [100, 350) and [600, 700)
    assert r["busy_s"] == pytest.approx(350e-9)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["launches"] == {"jit_step": [200, 100], "jit_head": [100]}
    assert r["op_self_s"]["jit_step/while"] == pytest.approx(70e-9)
    assert r["op_self_s"]["jit_step/paged_attention"] == pytest.approx(
        50e-9)
    assert r["op_self_s"]["jit_head/fusion"] == pytest.approx(100e-9)
    # gaps: [0,100) mid 50 -> engine.step; [350,600) mid 475 -> poll;
    # [700,1000) mid 850 -> engine.step
    assert r["idle_by_host_s"] == pytest.approx(
        {"engine.step": 400e-9, "frontdoor.poll": 250e-9})


def test_base_names():
    assert devtrace._base("jit_paged_decode_step(1234)") == \
        "jit_paged_decode_step"
    assert devtrace._base("%copy.68 = bf16[1,2] copy(bf16[1,2] %x)") == \
        "copy"
    assert devtrace._base("%paged_attention.5 = bf16[4] custom-call()") \
        == "paged_attention"


def test_recorded_v5e_trace():
    rec = json.loads((DATA / "trace_v5e.json").read_text())
    r = devtrace.reduce(rec["events"], rec["window_s"] * 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    decode = r["launches"]["jit_paged_decode_step"]
    assert len(decode) == 4
    # the kernels of the decode step sit inside its launches
    attn = r["op_self_s"]["jit_paged_decode_step/paged_attention"]
    assert 0 < attn < sum(decode) / 1e9
    assert set(r["idle_by_host_s"]) <= {"backend.decode",
                                        "backend.copy_blocks",
                                        "no host span"}
    total_idle = sum(r["idle_by_host_s"].values())
    assert total_idle == pytest.approx(r["window_s"] - r["busy_s"],
                                       rel=1e-6)
