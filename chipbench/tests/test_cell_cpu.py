"""A whole run of a cell on the CPU at the smoke size, the look for the
chip skipped: a sound program comes out correct, and so does not with
the timed path broken underneath, once per fault a served model can
have; and the float8 control reads above the limit."""
import time

import jax
import jax.numpy as jnp
import pytest

import control
import run
import smoke
from repro.launch import serve
from repro.kernels import ops
from repro.models import model as M


@pytest.fixture(autouse=True)
def small_device(monkeypatch):
    # the CPU reports no memory limit: stand in a budget of a few dozen
    # KV blocks at the smoke width
    monkeypatch.setattr(serve, "hbm_limit", lambda: 4 << 20)


def _run(seed=2**31 + 21, trace=False, index=0):
    return run.run(smoke.cell(index), seed, 5.0, trace, time.monotonic())


# per cell, the per-layer metrics that read the host and so read on the
# CPU too (the device-trace ones need the chip)
HOST_READ = {0: {"prefix_reuse_share", "compiles_in_window", "ttft_p90_s"},
             1: {"tpot_p90_ms", "engine_host_ms_per_step"}}


@pytest.mark.parametrize("index", [0, 1])
def test_sound_run_is_correct_and_reports_every_metric(index):
    out = _run(trace=True, index=index)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 5 and out["failed"] == 0
    assert HOST_READ[index] <= set(out["metrics"])
    if index == 0:
        assert out["metrics"]["prefix_reuse_share"]["value"] > 0
        assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert list(out)[-1] == "compared"
    e2e = _run(seed=5, index=index)
    assert set(e2e["metrics"]) == {m["name"] for m in
                                   smoke.cell(index).end_to_end}


def _state_unchanged(step):
    """The decode step leaves its state, the KV pools, as it found them:
    each layer's KV write returns the pool unchanged."""
    def unchanged(k_pages, v_pages, *_):
        return k_pages, v_pages
    ops.kv_token_write = unchanged
    return step


def _half_batch(step):
    """Half of the batch left out: the second half of the rows gets the
    first half's logits."""
    def fault(*a):
        logits, k, v = step(*a)
        n = logits.shape[0]
        h = n // 2
        if h:
            logits = logits.at[n - h:].set(logits[:h])
        return logits, k, v
    return fault


def _token_altered(step):
    """Every token altered where it is produced: logits shifted by one
    id, so the argmax lands on the next token."""
    def fault(*a):
        logits, k, v = step(*a)
        return jnp.roll(logits, 1, axis=-1), k, v
    return fault


@pytest.fixture
def broken(monkeypatch):
    """Break the timed path for one test; the step programs are traced
    anew on both sides of it."""
    monkeypatch.setattr(ops, "kv_token_write", ops.kv_token_write)
    jax.clear_caches()
    yield lambda fault: monkeypatch.setattr(
        M, "paged_decode_step", fault(M.paged_decode_step))
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_a_broken_timed_path_is_not_correct(broken, fault):
    broken(fault)
    out = _run(seed=2**31 + 33)
    assert not out["correct"], out["compared"]


def test_float8_control_reads_above_the_limit():
    c = smoke.cell()
    r = control.readings(c, 2**31 + 44, 5.0, time.monotonic())
    limit = c.load["check"]["max_logit_gap"]
    assert r["max_logit_gap"] <= limit < r["control_max_logit_gap"]
    # the decision run.py makes: the program correct, the control not
    assert r["correct"] and not r["control_correct"]
