"""chip_smoke.py's phases, run here on the CPU at a reduced size with the
Pallas kernels in interpret mode, and its refusal to run without a TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.kernels import ops
from repro.models.config import get_config
from repro.store import chunks as chunklib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def interpret(monkeypatch):
    """What a TPU host resolves ``backend=None`` to, with the kernels run
    by the Pallas interpreter instead of compiled."""
    monkeypatch.setattr(ops, "default_backend", lambda: "interpret")


def _small():
    return get_config("qwen3-0.6b").reduced(n_layers=2, dtype="bfloat16")


def test_phases_run_the_kernels_and_match(smoke, interpret, tmp_path,
                                          monkeypatch):
    # as at published widths, the embedding, the MLP weights and their
    # moments (bf16 and f32) go through the chunk layer; k/v stay whole
    monkeypatch.setattr(chunklib, "DEFAULT_CHUNK_THRESHOLD", 128 * 1024)
    before = ops.DISPATCHES.snapshot()
    params, train = smoke.phase_train(_small(), str(tmp_path), 0, 2, 16)
    assert train["ok"], train
    assert train["commit_depths"] == [0, 1]
    assert train["resumed_at_step"] == smoke.STEPS
    weights, lineage = smoke.phase_lineage(params, str(tmp_path), 0)
    assert lineage["ok"], lineage
    assert lineage["chain_depths"] == list(range(9))
    assert lineage["pool"]["fused_applies"] > 0
    _, kernels = smoke.phase_kernels(weights["layers/0/mlp/w_in"], 0)
    assert kernels["ok"], kernels
    after = ops.DISPATCHES.snapshot()
    for name in ("fingerprint", "snapshot_fused", "dequant_apply",
                 "chain_apply", "delta_quantize"):
        assert after[name] > before.get(name, 0), name


def test_sharded_phase_on_four_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util, json, jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.ops.default_backend = lambda: "interpret"
        cfg = smoke.get_config("qwen3-0.6b").reduced(n_layers=2,
                                                    dtype="bfloat16")
        _, line = smoke.phase_sharded(cfg, {str(tmp_path)!r}, 0, 4, 16,
                                      jax.devices())
        print(json.dumps(line))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["ok"], line
    assert line["mismatches"] == {"restored_leaves": 0,
                                  "restored_placement": 0}


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_refuses_to_run_without_a_tpu(tmp_path, alone):
    script = SCRIPT
    if alone:  # a directory holding chip_smoke.py and nothing of the repo
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if not alone:
        assert out.returncode == 2 and "no TPU" in out.stderr
