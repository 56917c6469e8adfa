"""Smoke run of the elastic trainer on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the elastic path, on four chips

One chip: trains xlstm_125m at its published widths through the normal
driver (``repro.launch.train``, batch 8, sequence 1024) and checks that
every loss is finite and the loss falls, that the chip's step-0 loss
matches the same initial params' loss on the host CPU within
``LOSS_RTOL``, and that the final params come back from a
``CheckpointManager`` save and restore bit for bit.

Four chips: runs ``ElasticTrainer`` over the ``steady-cycle`` scenario
(1 -> 4 -> 1 chips, twice), with an engine that charges the exact
parameter bytes (``PytreeBytesModel``), and checks that each reshard's
measured bytes equal its charged bytes, that every event's
``est_wall_s`` and ``downtime_s`` equal the simulator's, and that the
losses match a rigid run on one of the four chips: bit for bit before
the first resize, within ``LOSS_RTOL`` on the first step after it, and
within ``LOSS_RTOL_TAIL`` over the last ``TAIL`` steps.  It runs no
other phase.

Everything runs in this one process, which is the only one that touches
JAX.  Without a TPU the script exits non-zero before any work.  Times
printed here are host-clock diagnostics, compilation included.  The last
line of stdout is one JSON object naming the device, printed only when
every check passed.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "xlstm_125m"
BATCH, SEQ, STEPS = 8, 1024, 8
# One loss of the same params on the same batch, computed on another
# device arrangement (chip against host CPU, four chips against one):
# two bf16 ulps (2**-7), relative.  The model computes in bf16 and the
# arrangements round differently.
LOSS_RTOL = 2.0 ** -7
# Elastic against rigid, mean loss of the last TAIL steps, relative.
# After the first resize the two runs reduce gradients in another order,
# and early Adam steps amplify that rounding step by step: per-step
# losses drift apart by tens of percent, yet both runs train as well.  A
# reshard that scrambles or drops the state, or stops the training,
# moves the tail by far more.
LOSS_RTOL_TAIL = 0.1
TAIL = 5
SMOKE_DIR = ROOT / ".chip_smoke"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_memory(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


def one_chip() -> None:
    """Rigid training through the driver, checked against the host CPU
    and through a checkpoint round trip."""
    import jax
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.configs import arch_config
    from repro.data import SyntheticTokens
    from repro.launch import train
    from repro.models import Model

    ckpt_dir = SMOKE_DIR / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    argv = ["--arch", ARCH, "--full-config", "--steps", str(STEPS),
            "--batch", str(BATCH), "--seq", str(SEQ),
            "--checkpoint-dir", str(ckpt_dir), "--checkpoint-every", str(STEPS)]
    t0 = time.perf_counter()
    state, losses = train.main(argv)
    jax.block_until_ready(state)
    print(f"train: {STEPS} steps, batch {BATCH}, seq {SEQ}: "
          f"{time.perf_counter() - t0:.3f} s host clock, compilation included")
    print(f"losses: {losses}")
    print(f"peak device memory: {peak_memory(jax.devices()[0])}")
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"non-finite or missing losses: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    cfg = arch_config(ARCH)
    model = Model(cfg)
    cpu = jax.devices("cpu")[0]
    host_batch = SyntheticTokens(cfg, BATCH, SEQ).sample(0)
    with jax.default_device(cpu):
        params = jax.jit(lambda k: model.init(k)[0])(jax.random.key(0))
        ref = float(jax.jit(model.loss)(params, host_batch))
    delta = abs(losses[0] - ref)
    print(f"step-0 loss: chip {losses[0]!r}, cpu {ref!r}, "
          f"|delta| {delta!r} (limit {LOSS_RTOL * abs(ref)!r})")
    check(delta <= LOSS_RTOL * abs(ref),
          "chip step-0 loss disagrees with the host CPU")

    restored, step = CheckpointManager(str(ckpt_dir)).restore_latest(
        {"params": state.params})
    check(step == STEPS, f"latest checkpoint is step {step}, not {STEPS}")
    want = jax.tree_util.tree_flatten_with_path(jax.device_get(state.params))[0]
    got = jax.tree.leaves(jax.device_get(restored["params"]))
    check(len(want) == len(got), "restored tree has another leaf count")
    nbytes = 0
    for (path, a), b in zip(want, got):
        a, b = np.asarray(a), np.asarray(b)
        check(a.dtype == b.dtype and a.shape == b.shape
              and a.tobytes() == b.tobytes(),
              f"param {jax.tree_util.keystr(path)} differs after restore")
        nbytes += a.nbytes
    print(f"checkpoint: {len(want)} leaves, {nbytes} bytes, restored bit-identical")
    shutil.rmtree(ckpt_dir, ignore_errors=True)


def four_chip() -> None:
    """steady-cycle through ElasticTrainer, against the simulator and a
    rigid one-chip run."""
    import jax
    import numpy as np

    from repro.configs import arch_config
    from repro.elastic import (
        DevicePool,
        ElasticRuntime,
        ElasticTrainer,
        PytreeBytesModel,
        SimulatedRMS,
    )
    from repro.malleability import get_scenario, run_scenario_sim
    from repro.models import Model

    model = Model(arch_config(ARCH))
    scenario = get_scenario("steady-cycle")

    def engine():
        e = scenario.default_engine()
        e.bytes_model = PytreeBytesModel(model)
        return e

    sim = run_scenario_sim(scenario, engine=engine())
    trainer = ElasticTrainer.from_scenario(model, scenario, engine=engine(),
                                           batch=BATCH, seq=SEQ)
    t0 = time.perf_counter()
    trainer.run(scenario.steps)
    print(f"elastic: {scenario.steps} steps, batch {BATCH}, seq {SEQ}: "
          f"{time.perf_counter() - t0:.3f} s host clock, compilation included")
    live = trainer.runtime.history
    log = trainer.transfer_log
    check(len(live) == len(sim) == len(log) > 0,
          f"{len(live)} live events, {len(sim)} simulated, {len(log)} reshards")

    for rec, t in zip(live, log):
        print(f"resize {rec.kind} {rec.nodes_before}->{rec.nodes_after} at step "
              f"{t['step']}: measured {t['bytes_moved']} bytes moved, "
              f"charged {t['charged_bytes_moved']}")
        check(t["bytes_moved"] == t["charged_bytes_moved"],
              f"measured bytes differ from charged bytes at step {t['step']}")
    check(any(t["bytes_moved"] > 0 for t in log), "no resize moved any bytes")

    for s, rec in zip(sim, live):
        print(f"event {rec.kind} {rec.nodes_before}->{rec.nodes_after}: "
              f"est_wall_s live {rec.est_wall_s!r} sim {s.est_wall_s!r}, "
              f"downtime_s live {rec.downtime_s!r} sim {s.downtime_s!r}")
        check(s.est_wall_s == rec.est_wall_s and s.downtime_s == rec.downtime_s,
              f"live {rec.kind} event disagrees with the simulator")

    elastic = trainer.losses()
    del trainer
    rigid_runtime = ElasticRuntime(pool=DevicePool(devices=jax.devices()[:1]))
    rigid = ElasticTrainer(model=model, runtime=rigid_runtime,
                           rms=SimulatedRMS(), batch=BATCH, seq=SEQ)
    rigid.run(scenario.steps)
    reference = rigid.losses()
    print(f"losses elastic: {elastic}")
    print(f"losses rigid:   {reference}")
    print(f"peak device memory, chip 0: {peak_memory(jax.devices()[0])}")
    check(len(elastic) == len(reference) == scenario.steps
          and all(np.isfinite(elastic)) and all(np.isfinite(reference)),
          "missing or non-finite loss")

    # Before the first resize both runs are one program on one chip.
    first = log[0]["step"]
    check(elastic[:first] == reference[:first],
          f"losses before the first resize (step {first}) differ")
    # The first step after it sees the same params and batch on four chips.
    gap = abs(elastic[first] - reference[first]) / abs(reference[first])
    print(f"step {first}, first on four chips: relative loss gap {gap!r} "
          f"(limit {LOSS_RTOL!r})")
    check(gap <= LOSS_RTOL, f"step {first} loss on four chips disagrees")
    tail_e = float(np.mean(elastic[-TAIL:]))
    tail_r = float(np.mean(reference[-TAIL:]))
    tail_gap = abs(tail_e - tail_r) / tail_r
    print(f"mean loss of the last {TAIL} steps: elastic {tail_e!r}, rigid "
          f"{tail_r!r}, relative gap {tail_gap!r} (limit {LOSS_RTOL_TAIL!r})")
    check(tail_gap <= LOSS_RTOL_TAIL, "elastic run ends off the rigid run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the rigid driver on one chip; "
                         "4: the elastic steady-cycle path on four")
    args = ap.parse_args()

    # The step-0 reference runs on the host CPU backend of this process.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from repro.launch import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    try:
        if args.chips == 4:
            four_chip()
        else:
            one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
