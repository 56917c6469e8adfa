"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs real steps on the host's devices: the reduced config by default,
the published widths with ``--full-config`` (xlstm_125m fits one chip;
the larger configs only fit the production mesh, which the dry-run
exercises).  Integrates the elastic runtime: pass ``--scenario <name>`` to
run the malleable loop against a registered declarative workload trace
(grow/shrink/fail/straggler events planned by the ReconfigEngine).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax

from repro.configs import arch_config, smoke_config
from repro.data import SyntheticTokens, make_batch_on_mesh
from repro.launch import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.parallel.sharding import ShardingContext
from repro.train.steps import TrainState, build_init_fn, build_train_step
from repro.checkpoint import CheckpointManager


def main(argv: Optional[Sequence[str]] = None) -> tuple[TrainState, list[float]]:
    """Run the driver; returns the final train state and every step's loss."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full arch config (production scale)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--scenario", default=None,
                    help="run the elastic loop against a registered scenario "
                         "(see repro.malleability.registered_scenarios)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = arch_config(args.arch) if args.full_config else smoke_config(args.arch)
    model = Model(cfg)

    if args.scenario:
        return run_scenario(model, args)
    mesh = make_host_mesh(args.model_parallel)
    ctx = ShardingContext(mesh=mesh, mode="train")

    step_fn, shardings, _ = build_train_step(model, ctx, lr=args.lr)
    init_fn, _ = build_init_fn(model, ctx)
    state = init_fn(jax.random.key(0))
    step_jit = jax.jit(
        step_fn, in_shardings=(shardings, None), out_shardings=(shardings, None),
        donate_argnums=(0,),
    )

    ckpt = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
    data = SyntheticTokens(cfg, args.batch, args.seq)
    losses = []
    t0 = time.time()
    for i, host_batch in enumerate(data.iter()):
        if i >= args.steps:
            break
        batch = make_batch_on_mesh(host_batch, cfg, ctx)
        state, metrics = step_jit(state, batch)
        losses.append(metrics["loss"])
        if i % 10 == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {i:>5} loss {loss:.4f} ({(time.time()-t0):.1f}s)", flush=True)
        if ckpt and (i + 1) % args.checkpoint_every == 0:
            ckpt.save({"params": state.params}, i + 1)
    if ckpt:
        ckpt.wait()
    return state, [float(x) for x in jax.device_get(losses)]


def run_scenario(model: Model, args) -> tuple[TrainState, list[float]]:
    """Malleable training: the declarative trace drives the live runtime."""
    from repro.elastic import ElasticTrainer
    from repro.malleability import get_scenario

    scenario = get_scenario(args.scenario)
    trainer = ElasticTrainer.from_scenario(
        model, scenario, lr=args.lr, batch=args.batch, seq=args.seq,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    steps = max(args.steps, scenario.steps)
    t0 = time.time()
    hist = trainer.run(steps)
    for rec in trainer.runtime.history:
        print(f"reconfig {rec.kind:<10} {rec.mechanism:<22} "
              f"{rec.nodes_before}->{rec.nodes_after} nodes  "
              f"est {rec.est_wall_s*1e3:.2f} ms  downtime {rec.downtime_s*1e3:.2f} ms",
              flush=True)
    print(f"scenario {scenario.name!r}: {len(hist)} steps, "
          f"loss {hist[0].loss:.4f} -> {hist[-1].loss:.4f} "
          f"({time.time()-t0:.1f}s, {len(trainer.runtime.history)} reconfigs)",
          flush=True)
    return trainer.state, trainer.losses()


if __name__ == "__main__":
    main()
