"""The trace reduction against a small trace recorded on a TPU v5e chip:
two steps of ``stablelm_3b-4l`` at its smoke size, traced by the
harness (``benchmarks/chip/calibrate.py --trace-fixture``)."""
from __future__ import annotations

from pathlib import Path

import pytest

import smoke_run  # noqa: F401  (puts benchmarks/chip on the path)
import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "data" / "stablelm_smoke.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(str(FIXTURE))


@pytest.fixture(scope="module")
def reduced(profile):
    return trace_reduce.reduce(profile)


def _plane(profile, name):
    return next(p for p in profile.planes if p.name == name)


def test_window_is_the_harness_span(profile, reduced):
    host = _plane(profile, "/host:CPU")
    windows = [e for line in host.lines for e in line.events if e.name == "bench/window"]
    assert len(windows) == 1
    assert reduced["window_s"] == pytest.approx(windows[0].duration_ns * 1e-9)
    assert reduced["devices"] == 1


def test_busy_is_the_union_of_ops_inside_the_window(profile, reduced):
    host = _plane(profile, "/host:CPU")
    w = next(e for line in host.lines for e in line.events if e.name == "bench/window")
    w0, w1 = w.start_ns, w.start_ns + w.duration_ns
    ops = next(line for line in _plane(profile, "/device:TPU:0").lines
               if line.name == "XLA Ops")
    edges = []
    for e in ops.events:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
        if t > s:
            edges += [(s, 1), (t, -1)]
    busy, depth, since = 0.0, 0, None
    for x, d in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth == 0 and d > 0:
            since = x
        depth += d
        if depth == 0:
            busy += x - since
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)


def test_program_time_sums_the_modules_in_the_window(profile, reduced):
    host = _plane(profile, "/host:CPU")
    w = next(e for line in host.lines for e in line.events if e.name == "bench/window")
    w0, w1 = w.start_ns, w.start_ns + w.duration_ns
    mods = next(line for line in _plane(profile, "/device:TPU:0").lines
                if line.name == "XLA Modules")
    inside = [min(e.start_ns + e.duration_ns, w1) - max(e.start_ns, w0)
              for e in mods.events if e.name.startswith("jit_train_step(")]
    assert len(inside) == 2 and all(d > 0 for d in inside)
    assert reduced["programs"]["jit_train_step"] == pytest.approx(sum(inside) * 1e-9)


def test_breakdown_names_ops_and_gaps(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all(" " not in name and not name.startswith("%") for name, _ in ops)
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert {name for name, _ in gaps} <= {"data", "step", "sync", "trainer", "none"}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle * 1.0001


def test_a_trace_without_a_device_reduces_to_nothing():
    class Plane:
        name, lines = "/host:CPU", []

    class Profile:
        planes = [Plane()]

    assert trace_reduce.reduce(Profile()) == {}
