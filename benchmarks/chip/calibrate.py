"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 3] [--faults half,exchange] [--out FILE]

For each seed: the program's three compared steps (as a run's set-up
makes them) against the float32 reference; these sound runs give each
number's lower reading.  On the first ``--control-seeds`` seeds also:

- the control, the reference computed with float8 matmul operands in
  the program's place (the precision below the configuration's
  bfloat16), which gives the upper reading;
- each planted fault of ``--faults``: ``half`` (half of every batch
  left out, the mean taken over the rest), ``exchange`` (on steps over
  several chips only the first chip's rows, as without the gradient
  exchange) and ``unchanged`` (a step that returns its state unchanged).

Each reading is printed and appended to ``--out`` as one JSON line.  A
run of the benchmark never calls this.

    python3 benchmarks/chip/calibrate.py --trace-fixture FILE

records a two-step traced window of ``stablelm_3b-4l`` at its smoke size
on one chip and copies its ``.xplane.pb`` to FILE (the trace reduction's test
data).
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

import harness


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def program(cell, seed, devices, fault=None):
    trainer = harness.make_trainer(cell, seed, devices, harness.Spans(), fault=fault)
    readings = harness.program_readings(trainer, cell)
    del trainer
    gc.collect()
    return readings


def calibrate(cell, seeds, control_seeds, faults, devices, out, control=True):
    device = devices[0]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ref = harness.reference_readings(cell, seed, device=device)
        rows = [("program", program(cell, seed, devices))]
        if i < control_seeds and control:
            rows.append(("control", harness.reference_readings(
                cell, seed, precision="fp8", device=device)))
        if i < control_seeds:
            rows += [(f"fault:{f}", program(cell, seed, devices, fault=f)) for f in faults]
        for kind, readings in rows:
            numbers = harness.compare(readings, ref)
            line = {"workload": cell.name, "seed": seed, "kind": kind,
                    **{k: numbers[k] for k in ("loss_gap", "grad_gap", "update_gap")},
                    "worst": numbers["worst"], "still_leaves": numbers["still_leaves"],
                    "loss": readings["loss"], "ref_loss": ref["loss"],
                    "grad": readings["grad"], "ref_grad": ref["grad"]}
            print(json.dumps(line), flush=True)
            if out:
                with open(out, "a") as f:
                    f.write(json.dumps(line) + "\n")
        _log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")


def control_only(cell, seeds, device, out):
    for seed in seeds:
        ref = harness.reference_readings(cell, seed, device=device)
        control = harness.reference_readings(cell, seed, precision="fp8", device=device)
        numbers = harness.compare(control, ref)
        line = {"workload": cell.name, "seed": seed, "kind": "control",
                **{k: numbers[k] for k in ("loss_gap", "grad_gap", "update_gap")},
                "loss": control["loss"], "ref_loss": ref["loss"]}
        print(json.dumps(line), flush=True)
        if out:
            with open(out, "a") as f:
                f.write(json.dumps(line) + "\n")


def trace_fixture(dest: Path, devices):
    cell = harness.smoke_cell(harness.load_cell("stablelm_3b-4l.train-1chip"))
    spans = harness.Spans()
    trainer = harness.make_trainer(cell, 7, devices, spans, traced=True)
    trainer.run(3)
    import jax

    jax.block_until_ready(trainer.state)
    harness.start_profiler(harness.TRACE_DIR)
    try:
        window = harness.run_window(trainer, cell, spans, seconds=None, max_steps=2)
    finally:
        jax.profiler.stop_trace()
    dest.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(harness.find_trace(harness.TRACE_DIR), dest)
    spans_out = [s for s in spans.records if s["name"] in ("window", "step", "sync", "data")]
    print(json.dumps({"steps": window["steps"], "bytes": dest.stat().st_size,
                      "spans": spans_out[-12:]}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--no-control", action="store_true",
                    help="planted faults only on the first seeds (the control "
                         "does not depend on the cell's chips)")
    ap.add_argument("--control-only", action="store_true",
                    help="the reference and the control alone, on one chip")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out")
    ap.add_argument("--trace-fixture")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"calibrate: needs a TPU; JAX found {devices[0].platform}")
        return 1
    harness.use_compile_cache()
    if args.trace_fixture:
        trace_fixture(Path(args.trace_fixture), devices)
        return 0
    cell = harness.load_cell(args.workload)
    if args.control_only:
        control_only(cell, [int(s) for s in args.seeds.split(",") if s], devices[0], args.out)
        return 0
    if len(devices) < cell.chips:
        _log(f"calibrate: {cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
        return 1
    seeds = [int(s) for s in args.seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]
    calibrate(cell, seeds, args.control_seeds, faults, devices, args.out,
              control=not args.no_control)
    return 0


if __name__ == "__main__":
    sys.exit(main())

