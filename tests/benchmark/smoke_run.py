"""Drive one benchmark run at a cell's smoke size, on whatever devices JAX has.

    python tests/benchmark/smoke_run.py <cell> [--fault F] [--trace] [--limits JSON]

Everything a chip run does except the look for a chip: the cell's
trainer at its configuration's ``smoke`` sizes (batch 4, sequence 32),
the three compared steps, a one-second window (or the traced steps)
and the float32 reference.  Prints the result line.  The tests run it
in a subprocess where a cell needs several (virtual CPU) devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

SEED = 2**33 + 17
CPU_PEAK = {"bf16_flops_per_s": 1e12}
# At the smoke size on the CPU a sound run reads a grad_gap of about
# 0.0025 on SEED, above the resize cell's chip limit; every planted
# fault reads 0.03 or more on some number.
SMOKE_FLOOR = {"grad_gap": 0.005}


def smoke_limits(cell) -> dict:
    return {k: max(v, SMOKE_FLOOR.get(k, 0.0)) for k, v in cell.limits.items()
            if k in ("loss_gap", "grad_gap", "update_gap")}


def run(name: str, fault: str | None = None, trace: bool = False,
        limits: dict | None = None, seed: int = SEED) -> dict:
    import harness
    import jax

    cell = harness.smoke_cell(harness.load_cell(name))
    cell = dataclasses.replace(cell, limits=limits if limits is not None
                               else smoke_limits(cell))
    peak, harness.peak = harness.peak, lambda kind: CPU_PEAK
    try:
        return harness.run(cell, seed, 1.0, trace, jax.devices(), T0, fault=fault,
                           log=lambda m: print(m, file=sys.stderr))
    finally:
        harness.peak = peak


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--fault")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--limits")
    args = ap.parse_args()
    limits = json.loads(args.limits) if args.limits else None
    print(json.dumps(run(args.cell, args.fault, args.trace, limits)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
