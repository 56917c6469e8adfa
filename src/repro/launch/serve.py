"""Serving driver: ``python -m repro.launch.serve``.

Default mode drives the **elastic decode service** (:mod:`repro.serving`):
replays one (or all) registered serve traffic traces — the decode pool
grown/shrunk by the traffic policy, in-flight KV caches migrated and
priced on every resize — on the simulator and the live runtime, prints
per-phase latency/throughput, and exits non-zero if the two executors
disagree on ANY number (the same contract as
``examples/malleability_sim.py``).

``--static`` keeps the original single-shot decode path: batched greedy
decoding over synthetic prompts on the host's devices, reporting
prefill/decode throughput.
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence


def _run_static(args: argparse.Namespace) -> int:
    """The legacy single-shot decode driver (JAX imported lazily)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.models import Model

    cfg = smoke_config(args.arch).replace(embed_inputs=False)
    model = Model(cfg)
    params, _ = model.init(jax.random.key(0))
    B, P, G = args.batch, args.prompt_len, args.gen_len
    cache = model.init_cache(B, P + G)
    decode = jax.jit(model.decode_step)
    prompts = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab)

    def tok_batch(tokens, t):
        pos = jnp.full((B, 1), t, jnp.int32)
        out = {"tokens": tokens, "cache_pos": jnp.int32(t),
               "positions": jnp.stack([pos, pos, pos]) if cfg.mrope_sections else pos}
        return out

    t0 = time.time()
    logits = None
    for t in range(P):
        logits, cache = decode(params, cache, tok_batch(prompts[:, t:t + 1], t))
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    generated = [nxt]
    t0 = time.time()
    for t in range(P, P + G - 1):
        logits, cache = decode(params, cache, tok_batch(nxt, t))
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        generated.append(nxt)
    jax.block_until_ready(nxt)
    t_decode = time.time() - t0

    gen = jnp.concatenate(generated, axis=1)
    print(f"arch={cfg.name} batch={B}")
    print(f"prefill: {P} steps in {t_prefill:.2f}s")
    print(f"decode:  {B * (G - 1) / max(t_decode, 1e-9):.1f} tok/s "
          f"({G - 1} steps in {t_decode:.2f}s)")
    print(f"sample output ids: {gen[0, :12].tolist()}")
    return 0


def print_serve_report(rep) -> None:
    """Per-phase table + totals for one serve replay."""
    print(f"[{rep.executor}] {rep.scenario}: {rep.submitted} requests, "
          f"{rep.completed} completed, {rep.dropped} dropped "
          f"({rep.migrated} migrated / {rep.requeued} requeued on resizes)")
    print(f"  {'steps':>12} {'workers':>7} {'done':>5} "
          f"{'p50 lat':>9} {'tok/s':>8}")
    for ph in rep.phases:
        print(f"  [{ph.start_step:4d},{ph.end_step:4d}) {ph.workers:7d} "
              f"{ph.completed:5d} {ph.p50_latency_s:8.3f}s "
              f"{ph.throughput_tok_s:8.1f}")
    print(f"  total: wall {rep.wall_s:.2f}s, downtime {rep.downtime_s:.4f}s, "
          f"queued {rep.queued_s:.2f}s, p50 {rep.p50_latency_s:.3f}s, "
          f"p99 {rep.p99_latency_s:.3f}s, {rep.throughput_tok_s:.1f} tok/s, "
          f"{rep.bytes_moved / 1e6:.1f} MB KV moved "
          f"({rep.bytes_cross_rack / 1e6:.1f} MB cross-rack)")


def run_elastic(names: Sequence[str], executor: str,
                strategy: Optional[str]) -> int:
    """Replay serve traces; returns the number of sim/live disagreements."""
    from repro.serving import run_serve, serve_parity_key

    bad = 0
    for name in names:
        if executor in ("sim", "live"):
            print_serve_report(run_serve(name, executor=executor,
                                         strategy=strategy))
            continue
        sim = run_serve(name, executor="sim", strategy=strategy)
        live = run_serve(name, executor="live", strategy=strategy)
        print_serve_report(live)
        if serve_parity_key(sim) == serve_parity_key(live):
            print(f"  sim == live: OK ({len(live.records)} resizes, "
                  f"{live.completed} requests, every number identical)")
        else:
            bad += 1
            print(f"  sim == live: DISAGREE on {name!r}", file=sys.stderr)
    return bad


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--static", action="store_true",
                    help="legacy single-shot decode (needs --arch)")
    ap.add_argument("--arch", default="",
                    help="model config (static mode only)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--scenario", default="all",
                    help="serve trace name, or 'all' (elastic mode)")
    ap.add_argument("--executor", choices=("sim", "live", "both"),
                    default="both", help="elastic-mode executor(s)")
    ap.add_argument("--strategy", default=None,
                    help="spawn strategy override (elastic mode)")
    args = ap.parse_args(argv)

    if args.static:
        if not args.arch:
            ap.error("--static requires --arch")
        from repro.launch import use_compile_cache

        use_compile_cache()
        return _run_static(args)

    from repro.malleability.policies import SERVE_SCENARIO_NAMES

    names = (SERVE_SCENARIO_NAMES if args.scenario == "all"
             else (args.scenario,))
    return run_elastic(names, args.executor, args.strategy)


if __name__ == "__main__":
    sys.exit(main())
