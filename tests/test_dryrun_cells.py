"""Multi-pod dry-run smoke: one representative cell per step kind compiles
on the production meshes (the full 40-cell x 2-mesh sweep runs via
``python -m repro.launch.dryrun --all --both-meshes``; artifacts in
EXPERIMENTS.md)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CASES = [
    ("qwen2.5-3b", "train_4k", []),
    ("qwen2.5-3b", "decode_32k", ["--multipod"]),
]


@pytest.mark.dryrun
@pytest.mark.slow
@pytest.mark.parametrize("arch,shape,extra", CASES)
def test_cell_compiles(arch, shape, extra, tmp_path):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", str(tmp_path)] + extra
    # the dry-run fakes 512 host devices: its child never touches a chip
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                         timeout=560, env=env)
    assert "ALL CELLS PASS" in out.stdout, out.stdout[-3000:] + out.stderr[-3000:]
    arts = list(tmp_path.glob("*.json"))
    assert arts
    art = json.loads(arts[0].read_text())
    assert art["roofline"]["bound_s"] > 0
    assert art["memory"]["peak_bytes_per_device"] > 0
    assert art["collectives"]["total_link_bytes"] > 0
