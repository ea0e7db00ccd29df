"""``chip_smoke.py`` phases b–d in-process on the CPU at stablelm-3b's
smoke width, through the same functions the chip run calls; and the
script's refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.configs.base import get_smoke_config
from repro.launch import serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LIMIT = 8 << 20     # a device budget for a pool of a few dozen blocks


@pytest.fixture(scope="module")
def engine(smoke):
    # the CPU reports no memory limit: stand in a small device budget
    mp = pytest.MonkeyPatch()
    mp.setattr(serve, "hbm_limit", lambda: LIMIT)
    try:
        yield smoke.build(get_smoke_config("stablelm_3b"), seed=0)
    finally:
        mp.undo()


def test_pool_sized_from_device_budget(engine):
    be = engine.backend
    block = serve._block_bytes(be.cfg, engine.platform.block_tokens)
    params = sum(x.nbytes for x in jax.tree.leaves(be.params))
    budget = LIMIT * (1 - serve.HBM_MARGIN) - params
    n = engine.cfg.gpu_blocks
    # the largest pool that fits twice over (resident K and V pools plus
    # the steps' pool-sized temporaries); the CPU pads nothing
    assert 4 * block * (n + 1) <= budget < 4 * block * (n + 2)
    assert be.cache.k.nbytes == block * (n + 1)
    assert engine.cfg.host_blocks == min(
        2 * n, serve.HOST_TIER_BYTES // (2 * block))


def test_smoke_phases_cpu(smoke, engine):
    served = smoke.phase_serve(engine, seed=0, prefix_len=64,
                               lens=(96, 128, 80), max_tokens=16,
                               graph_prompt=160, timeout=600)
    assert served["requests_served"] >= 4
    assert served["offloads"] >= 1 and served["uploads"] >= 1
    logits, blocks = smoke.phase_logits(engine, seed=0, n_tokens=64)
    assert logits["top1_paged"] == logits["top1_ref"]
    mig = smoke.phase_migration(engine, blocks)
    assert mig["bit_exact"] and mig["blocks"] == len(blocks)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert r.stdout.strip() == ""
