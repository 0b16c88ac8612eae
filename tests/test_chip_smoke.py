"""chip_smoke.py's phases on the CPU, at the tiny qwen2.5 config with the
Pallas kernels in interpret mode: a raw resume is bit-identical to an
uninterrupted run, and a q8-delta resume is within half a block scale.
Only ``main`` insists on a TPU."""
import dataclasses
import importlib.util
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]
# vocabulary above the data's 512-token Markov alphabet, as at the chip
# config: the unseen embedding rows keep zero moments, so the q8-delta
# chain carries delta frames and not only keyframes
CFG = dataclasses.replace(get_config("qwen2.5-3b", tiny=True),
                          vocab_size=1024)
SHAPE = ShapeConfig("chip_smoke_cpu", "train", 32, 2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference(smoke):
    return smoke.phase_reference(CFG, SHAPE, impl="interpret")


def test_raw_resume_is_bit_identical(smoke, reference):
    out = smoke.phase_raw(CFG, SHAPE, reference, impl="interpret")
    assert out["bit_identical"]
    assert out["losses"] == reference["losses"][smoke.K:]


def test_q8_delta_resume_within_half_scale(smoke, reference):
    out = smoke.phase_q8_delta(CFG, SHAPE, reference, impl="interpret")
    assert out["worst_half_scale_ratio"] <= 1.0
    assert out["longest_chain"] >= 3


def test_main_refuses_to_run_without_a_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert '"ok"' not in captured.out


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # sets nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == use_compile_cache() \
            == str(ROOT / ".jax_cache") == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


RESIZE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.configs import get_config
from repro.configs.base import ShapeConfig
cfg = dataclasses.replace(get_config("qwen2.5-3b", tiny=True), vocab_size=1024)
out = smoke.phase_resize(cfg, ShapeConfig("t", "train", 32, 4),
                         impl="interpret")
assert [r["ranks"] for r in out["resizes"]] == [4, 2], out
print("RESIZE_OK")
"""


def test_resize_phase_on_four_cpu_devices():
    """The --chips 4 phase on four host devices (a child process, so this
    process keeps its one device): 2 -> 4 -> 2 ranks, the committed state
    on every device after each resize."""
    out = subprocess.run([sys.executable, "-c", RESIZE,
                          str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, cwd=ROOT,
                         timeout=300)
    assert "RESIZE_OK" in out.stdout, out.stdout[-3000:] + out.stderr[-3000:]
