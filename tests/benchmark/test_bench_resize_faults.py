"""The resize cell's faults, on four virtual CPU devices: a step that
returns its state unchanged, half of each batch left out, and the
gradient exchange between chips left out.  Each makes `correct` false."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import smoke_run

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("fault", ["unchanged", "half", "exchange"])
def test_resize_fault_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, smoke_run.__file__, "stablelm_3b-4l.resize-1to4",
                        "--fault", fault],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert not r["correct"], r["checks"]
    assert r["attempted"] % 8 == 0   # the window holds whole cycles of 2 x 4 steps
