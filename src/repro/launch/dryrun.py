import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count and the
# platform on first init.  The dry-run simulates a 512-chip mesh on host
# devices, so it never takes an accelerator from another process.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the appropriate step (train_step for train shapes, forward
     for prefill, serve_step for decode shapes) with full shardings,
  3. compiles, printing ``memory_analysis()`` (fits?) and
     ``cost_analysis()`` (FLOPs/bytes for the roofline),
  4. parses the post-SPMD HLO for collective operand bytes,
  5. writes a JSON record consumed by benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch stablelm_3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun     # driver mode
"""
import argparse
import json
import re
import sys
import time
import traceback


HW = {
    "peak_flops_bf16": 197e12,   # TPU v5e per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind result bytes from post-SPMD HLO.

    Convention (documented in EXPERIMENTS.md): bytes = result-shape bytes
    per op; all-reduce counted twice (ring = 2(N-1)/N ~ 2x buffer).  Ops
    inside loop bodies (scan-over-layers) are multiplied by the loop trip
    count parsed from the enclosing while op's induction bound when
    detectable; XLA names scan bodies ``body``/``region`` — we instead rely
    on layer-stacked collectives appearing inside the while body ONCE with
    per-iteration shapes, so we scale by the scan length recorded by the
    caller.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") or " = " in s:
            m = re.search(r"=\s*((?:\([^)]*\))|(?:\S+))\s+([a-z0-9-]+)", s)
            if not m:
                continue
            result_type, opcode = m.group(1), m.group(2)
            # normalize fused/async variants like all-gather-start
            base = None
            for c in _COLLECTIVES:
                if opcode == c or opcode.startswith(c + "-start"):
                    base = c
                    break
            if base is None:
                continue
            nbytes = _shape_bytes(result_type)
            if base == "all-reduce":
                nbytes *= 2
            out[base] += nbytes
            counts[base] += 1
    out_total = sum(out.values())
    return {"per_kind": out, "counts": counts, "total_bytes": out_total}


def run_cell(arch: str, shape_name: str, mesh_kind: str) -> dict:
    import jax

    from repro.configs import arch_config, SHAPES, shape_skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, sharding_mode
    from repro.models import Model
    from repro.parallel.sharding import ShardingContext, resolve_spec
    from repro.train.steps import (
        abstract_cache,
        batch_shardings,
        build_serve_step,
        build_train_step,
        cache_shardings,
        serving_param_shapes,
    )
    from repro.parallel.sharding import param_sharding_abstract
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape = next(s for s in SHAPES if s.name == shape_name)
    skip = shape_skip_reason(arch, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": skip}

    cfg = arch_config(arch)
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    ctx = ShardingContext(mesh=mesh, mode=sharding_mode(shape))
    specs = input_specs(cfg, shape)

    t0 = time.time()
    if shape.kind == "train":
        step, state_shardings, abstract_state = build_train_step(model, ctx)
        b_shard = batch_shardings(cfg, ctx, shape.global_batch, shape.seq_len)
        fn = jax.jit(
            step,
            in_shardings=(state_shardings, b_shard),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )
        lowered = fn.lower(abstract_state, specs)
    elif shape.kind == "prefill":
        shapes, pspecs = serving_param_shapes(model)
        p_shard = param_sharding_abstract(shapes, pspecs, ctx)
        b_shard = batch_shardings(cfg, ctx, shape.global_batch, shape.seq_len)

        def prefill(params, batch):
            from repro.parallel.sharding import use_sharding
            with use_sharding(ctx):
                logits, caches = model.forward(params, batch, collect_kv=True)
                return logits[:, -1:], caches

        fn = jax.jit(prefill, in_shardings=(p_shard, b_shard))
        lowered = fn.lower(shapes, specs)
    else:  # decode / long_decode
        shapes, pspecs = serving_param_shapes(model)
        p_shard = param_sharding_abstract(shapes, pspecs, ctx)
        serve = build_serve_step(model, ctx)
        cache = abstract_cache(model, shape.global_batch, shape.seq_len)
        c_shard = cache_shardings(model, ctx, shape.global_batch, shape.seq_len)
        tok_shard = {}
        for name, sds in specs.items():
            if name == "cache_pos":
                tok_shard[name] = NamedSharding(mesh, P())
            elif name == "positions" and cfg.mrope_sections:
                tok_shard[name] = NamedSharding(
                    mesh, resolve_spec((None, "batch", "seq"), sds.shape, ctx, "act"))
            elif name == "embeds":
                tok_shard[name] = NamedSharding(
                    mesh, resolve_spec(("batch", "seq", "embed"), sds.shape, ctx, "act"))
            else:
                tok_shard[name] = NamedSharding(
                    mesh, resolve_spec(("batch", "seq"), sds.shape, ctx, "act"))
        fn = jax.jit(
            serve,
            in_shardings=(p_shard, c_shard, tok_shard),
            donate_argnums=(1,),
        )
        lowered = fn.lower(shapes, cache, specs)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    # While-aware analysis: cost_analysis() counts scan bodies once on this
    # XLA build; `analyze` multiplies by loop trip counts (hlo_analysis.py).
    from repro.launch.hlo_analysis import analyze

    deep = analyze(hlo)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
            # deep = while-aware dot count; raw = XLA cost_analysis (counts
            # loop bodies once but sees fused non-dot matmuls).  Decode has
            # no layer loop, so raw is the better bound there; train is
            # loop-dominated, so deep is.  Record the max as the estimate.
            "flops": max(deep["flops"], float(cost.get("flops", 0.0))),
            "flops_deep": deep["flops"],
            "dot_bytes": deep["dot_bytes"],
            "xla_cost_flops_raw": float(cost.get("flops", 0.0)),
            "xla_bytes_accessed_raw": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": deep["collectives"],
        "hw": HW,
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    try:
        rec = run_cell(args.arch, args.shape, args.mesh)
    except Exception as e:  # a failed cell is a bug in the system: report it
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    js = json.dumps(rec, indent=2)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)
    sys.exit(0 if rec.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
