"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark reads.

- The traced window is the harness's ``bench/window`` span on the host.
- A device's busy time is the union of its ``XLA Ops`` intervals inside
  the window (``XLA Modules`` where a plane has no op line); busy and
  idle are averaged over the devices in the trace.
- Per-program device time sums the ``XLA Modules`` events by program
  name (``jit_train_step(12)`` counts as ``jit_train_step``).
- ``device_ops`` are the ten HLO instructions with the most device time;
  ``idle_gaps`` the ten longest gaps on the first device, each named by
  the innermost harness span (``bench/...``) open at its middle.
"""
from __future__ import annotations

import re
from collections import defaultdict

SPAN_PREFIX = "bench/"
WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(profile) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((n, s, e) for n, s, e in _events(line)
                             if n.startswith(SPAN_PREFIX))
        elif plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines or MODULES_LINE in lines:
                devices.append((plane.name, lines))
    if not devices:
        return {}
    devices.sort(key=lambda d: d[0])
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        w0, w1 = windows[0]
    else:
        w0 = min(s for _, lines in devices for line in lines.values()
                 for _, s, _ in _events(line))
        w1 = max(e for _, lines in devices for line in lines.values()
                 for _, _, e in _events(line))

    busy, programs, ops, gaps = [], defaultdict(float), defaultdict(float), []
    for i, (_, lines) in enumerate(devices):
        line = lines.get(OPS_LINE) or lines[MODULES_LINE]
        ivs = []
        for name, s, e in _events(line):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ivs.append((s, e))
                ops[_op_name(name)] += (e - s) * 1e-9
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE]):
                s, e = max(s, w0), min(e, w1)
                if e > s:
                    programs[_program_name(name)] += (e - s) * 1e-9
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    n = len(devices)
    inner = [(n_, s, e) for n_, s, e in spans if n_ != WINDOW]

    def holder(t):
        open_ = [(s, n_) for n_, s, e in inner if s <= t < e]
        return max(open_)[1][len(SPAN_PREFIX):] if open_ else "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n,
        "devices": n,
        "programs": {k: v / n for k, v in programs.items()},
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[holder((s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:TOP]],
    }
