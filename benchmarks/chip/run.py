"""Chip benchmark of the elastic trainer: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's trainer with weights from the seed, runs the
three steps that ``correct`` compares (through the trainer's own call
and feed) and every shape the window uses.  The window then trains
whole cycles of the traffic's resize schedule until ``--seconds`` have
passed; with ``--trace 1`` it instead records the traffic's
``trace_steps`` steps with the profiler and reports the per-layer
metrics.  Afterwards a plain float32 reference trains the same three
steps from the seed and the readings are compared.

The last line of stdout is the result as one JSON object; the numbers
compared, each with its limit, are the last lines of stderr.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result
and exits 1.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"run: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} device(s), platform {dev.platform!r}, "
              f"kind {dev.device_kind!r}", file=sys.stderr)
        return 1
    harness.peak(dev.device_kind)
    cache = harness.use_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices,
                         T_PROCESS, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
