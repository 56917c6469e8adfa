"""The benchmark's harness: one cell's set-up, measured window and check.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
its limits are ``limits/<cell>.json`` and each per-layer metric is read
by ``metrics/<metric>.py``.  The harness finds all of them by name.

Every run drives the program's elastic path: ``ElasticTrainer.run``
handles the resize events (``repro.elastic.runtime`` and
``repro.core.engine``), reshards the whole ``TrainState``, re-jits and
steps.  The subclass below only wraps those calls in the harness's
spans, takes its weights from the seed and its batches from the
harness's stream.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import flops  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from reference import common as ref_common  # noqa: E402

MANIFEST = ROOT / "BENCHMARK.json"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
COMPARED_STEPS = 3
# Steps the RMS script covers; a window runs a few hundred at most.
EVENT_HORIZON = 4096
# Leaves whose reference gradient is below this share of the median
# leaf's move under Adam by rounding alone; their change is not compared.
STILL_LEAF = 1e-3


# --------------------------------------------------------------- cells --
@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: traffic_mod.Traffic
    limits: dict
    manifest: dict

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def family(self) -> str:
        return self.config["reference"]


def load_manifest() -> dict:
    return json.loads(MANIFEST.read_text())


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest if manifest is not None else load_manifest()
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in manifest["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")
    config = json.loads((HERE / "configs" / f"{entry['config']}.json").read_text())
    limits_path = HERE / "limits" / f"{name}.json"
    limits = json.loads(limits_path.read_text()) if limits_path.exists() else {}
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic_mod.load(entry["traffic"]), limits=limits,
                manifest=manifest)


def smoke_cell(cell: Cell, batch: int = 4, seq: int = 32) -> Cell:
    """The cell at the configuration's ``smoke`` sizes, for tests on the CPU."""
    config = dict(cell.config, model=dict(cell.model, **cell.config["smoke"]))
    return dataclasses.replace(cell, config=config, traffic=dataclasses.replace(
        cell.traffic, batch=batch, seq=seq))


def reference_module(cell: Cell):
    return importlib.import_module(f"reference.{cell.family}")


def program_config(cell: Cell):
    """The program's ``ModelConfig``: the registry arch with every size of
    the configuration file applied."""
    from repro.configs import arch_config

    return arch_config(cell.config["arch"]).replace(**cell.model)


# --------------------------------------------------------------- spans --
class Spans:
    """Host spans, kept in memory and mirrored into the profiler trace."""

    def __init__(self):
        self.records: list[dict] = []
        self.tag: str | None = None
        self.event = -1

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            yield
        self.add(name, t0, time.perf_counter())

    def add(self, name: str, t0: float, t1: float):
        self.records.append({"name": name, "t0": t0, "t1": t1,
                             "tag": self.tag, "event": self.event})


class SpannedStream:
    def __init__(self, stream, spans: Spans):
        self.stream, self.spans = stream, spans

    def sample(self, step: int) -> dict:
        with self.spans.span("data"):
            return self.stream.sample(step)


def make_trainer(cell: Cell, seed: int, devices, spans: Spans, *,
                 traced: bool = False, fault: str | None = None):
    """The program's trainer for this cell: a pool of ``cell.chips`` chips,
    one per node, with the traffic's resizes scripted as RMS events."""
    from repro.elastic import (
        DevicePool,
        ElasticRuntime,
        ElasticTrainer,
        Event,
        EventKind,
        SimulatedRMS,
    )
    from repro.models import Model
    from repro.optim import AdamWState
    from repro.train.steps import TrainState, build_train_step, train_state_shardings

    t = cell.traffic
    table = reference_module(cell).param_table(cell.model)

    class BenchTrainer(ElasticTrainer):
        def _make_ctx(self):
            if not hasattr(self, "spans"):
                return super()._make_ctx()
            with self.spans.span("runtime"):
                return super()._make_ctx()

        def _handle(self, ev):
            with self.spans.span("runtime"):
                return super()._handle(ev)

        def _init_state(self):
            _, shardings = train_state_shardings(self.model, self._ctx)

            def init(key):
                params = ref_common.init_params(table, key)
                zeros = jax.tree.map(jax.numpy.zeros_like, params)
                step = jax.numpy.zeros((), jax.numpy.int32)
                return TrainState(params=params, step=step, opt=AdamWState(
                    step=step, mu=zeros, nu=jax.tree.map(jax.numpy.zeros_like, params)))

            self._state = jax.jit(init, out_shardings=shardings)(
                ref_common.seed_key(self.seed))
            self._rejit()

        def _reshard_state(self, step=-1, charged_bytes=0):
            with self.spans.span("reshard"):
                super()._reshard_state(step=step, charged_bytes=charged_bytes)
                if traced:
                    jax.block_until_ready(self._state)
            self.first_pending = True
            if self.after_reshard is not None:
                self.after_reshard(self)

        def _rejit(self):
            shardings = super()._rejit()
            if fault == "unchanged":
                step_fn, _, _ = build_train_step(self.model, self._ctx, lr=self.lr)

                def broken(state, batch):
                    return state, step_fn(state, batch)[1]

                self._step_fn = jax.jit(broken, in_shardings=(shardings, None),
                                        out_shardings=(shardings, None))
            jitted = self._step_fn

            def step(state, batch):
                if self.first_pending:
                    self.first_t0 = time.perf_counter()
                    self.first_pending = False
                with self.spans.span("step"):
                    return jitted(state, batch)

            self._step_fn = step
            return shardings

    script = []
    for step, before, after in t.resizes(EVENT_HORIZON):
        if after > before:
            script.append(Event(step=step, kind=EventKind.GROW, target_nodes=after))
        else:
            script.append(Event(step=step, kind=EventKind.SHRINK,
                                nodes=tuple(range(after, before))))
    model = Model(program_config(cell))
    ref_common.check_layout(table, model.abstract_params()[0])
    runtime = ElasticRuntime(pool=DevicePool(devices=list(devices)[:cell.chips]),
                             initial_nodes=t.start)
    trainer = BenchTrainer(model=model, runtime=runtime, rms=SimulatedRMS(script=script),
                           lr=cell.config["lr"], batch=t.batch, seq=t.seq, seed=seed)
    trainer.spans = spans
    trainer.after_reshard = None
    trainer.first_pending = False
    trainer.first_t0 = None
    trainer._data = SpannedStream(token_stream(cell, seed, fault), spans)
    return trainer


def token_stream(cell: Cell, seed: int, fault: str | None = None):
    """The cell's batches.  ``half`` drops the labels of half the rows on
    every step (of the second half of the sequence, where a batch is one
    row); ``exchange`` keeps, on steps spread over several chips, only
    the first chip's rows, as a step without its gradient exchange would
    see them."""
    t = cell.traffic
    masked = None
    if fault == "half":
        def masked(step):
            if t.batch == 1:
                return (slice(None), slice(t.seq // 2, None))
            return slice(t.batch // 2, None)
    elif fault == "exchange":
        def masked(step):
            n = t.allocation(step)
            return slice(t.batch // n, None) if n > 1 else None
    return traffic_mod.TokenStream(cell.model["vocab"], t.batch, t.seq, seed, masked)


# ------------------------------------------------------------ readings --
def program_readings(trainer, cell: Cell) -> dict:
    """Run the three compared steps through the trainer's own call and
    feed, and read the losses, each leaf's first gradient (from Adam's
    first moment after one step, read after the reshard that comes
    before step 1, if one does) and each leaf's change after three."""
    b1 = ref_common.ADAM["b1"]
    table = reference_module(cell).param_table(cell.model)
    grad_norms = jax.jit(lambda mu: ref_common.leaf_norms(
        jax.tree.map(lambda m: m / (1 - b1), mu)))
    change = jax.jit(lambda p, key: ref_common.leaf_norms(jax.tree.map(
        jax.numpy.subtract, p, ref_common.init_params(table, key))))
    got = {}

    def read(tr):
        got["grad"] = {k: float(v) for k, v in grad_norms(tr.state.opt.mu).items()}
        tr.after_reshard = None

    t = cell.traffic
    for i in range(COMPARED_STEPS):
        trainer.run(1)
        if i == 0:
            if t.allocation(1) != t.allocation(0):
                trainer.after_reshard = read
            else:
                read(trainer)
    moved = change(trainer.state.params, ref_common.seed_key(trainer.seed))
    return {"loss": [r.loss for r in trainer.history[:COMPARED_STEPS]],
            "grad": got["grad"], "update": {k: float(v) for k, v in moved.items()}}


def reference_readings(cell: Cell, seed: int, precision: str = "f32", device=None) -> dict:
    return ref_common.train_readings(
        reference_module(cell), cell.model, seed, token_stream(cell, seed),
        steps=COMPARED_STEPS, lr=cell.config["lr"],
        rows=int(cell.config["reference_rows"]), precision=precision, device=device)


def _worst_leaf(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    median = float(np.median([ref[k] for k in ref]))
    worst, which = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], median)
        if not gap <= worst:   # a NaN gap is the worst
            worst, which = gap, k
    return worst, which


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` holds to their limits, and where each was worst."""
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["loss"], ref["loss"]))
    grad, grad_leaf = _worst_leaf(prog["grad"], ref["grad"], ref["grad"])
    g_median = float(np.median(list(ref["grad"].values())))
    moving = [k for k, g in ref["grad"].items() if g >= STILL_LEAF * g_median]
    update, update_leaf = _worst_leaf(prog["update"], ref["update"], moving)
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": update,
            "worst": {"grad": grad_leaf, "update": update_leaf},
            "still_leaves": sorted(set(ref["grad"]) - set(moving))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit; a number without a limit fails."""
    checks = {name: {"value": numbers[name], "limit": limits.get(name)}
              for name in ("loss_gap", "grad_gap", "update_gap")}
    ok = all(c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# -------------------------------------------------------------- window --
def _sync(trainer, spans: Spans):
    with spans.span("sync"):
        jax.block_until_ready(trainer.state)
    return time.perf_counter()


def run_window(trainer, cell: Cell, spans: Spans, *, seconds: float | None,
               max_steps: int | None = None) -> dict:
    """Drive ``ElasticTrainer.run`` one step at a time for whole cycles of
    the traffic's schedule, until the first cycle that ends after the
    deadline (or for ``max_steps``).  A window cut inside a cycle would
    count a shrink of many seconds, or not, by where the deadline fell.
    The harness syncs only at the two boundaries of each resize and at
    the end of the window."""
    t = cell.traffic
    step = len(trainer.history)
    alloc = t.allocation(step - 1)
    t_start = time.perf_counter()
    deadline = t_start + seconds if seconds is not None else math.inf
    last_sync, since, n_steps = t_start, 0, 0
    segments, events, step_ends = [], [], []
    with spans.span("window"):
        while max_steps is None or n_steps < max_steps:
            if t.opens_cycle(step) and time.perf_counter() >= deadline:
                break
            new = t.allocation(step)
            if new != alloc:
                t0 = _sync(trainer, spans)
                segments.append((alloc, since, t0 - last_sync))
                kind = "expand" if new > alloc else "shrink"
                spans.tag, spans.event = kind, len(events)
                with spans.span("trainer"):
                    trainer.run(1)
                t1 = _sync(trainer, spans)
                if trainer.first_t0 is not None:
                    spans.add("first_step", trainer.first_t0, t1)
                    trainer.first_t0 = None
                spans.tag = None
                events.append({"kind": kind, "chips": new, "seconds": t1 - t0})
                last_sync, since, alloc = t1, 0, new
            else:
                with spans.span("trainer"):
                    trainer.run(1)
                since += 1
            step += 1
            n_steps += 1
            step_ends.append(time.perf_counter() - t_start)
        t_end = _sync(trainer, spans)
    segments.append((alloc, since, t_end - last_sync))
    return {"steps": n_steps, "seconds": t_end - t_start, "segments": segments,
            "events": events, "first_step": step - n_steps, "step_ends": step_ends}


def end_to_end(cell: Cell, window: dict) -> dict:
    t = cell.traffic
    out = {"train_tokens_per_s": window["steps"] * t.batch * t.seq / window["seconds"]}
    per_alloc = {}
    for chips, steps, secs in window["segments"]:
        s = per_alloc.setdefault(chips, [0, 0.0])
        s[0] += steps
        s[1] += secs
    for kind in ("expand", "shrink"):
        stalls = [e["seconds"] - per_alloc[e["chips"]][1] / per_alloc[e["chips"]][0]
                  for e in window["events"]
                  if e["kind"] == kind and per_alloc.get(e["chips"], [0])[0] > 0]
        if stalls:
            out[f"{kind}_stall_s"] = sum(stalls) / len(stalls)
    return out


# ------------------------------------------------------------- metrics --
def metrics_for(cell: Cell, kind: str) -> list[dict]:
    """The manifest's ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in cell.manifest[kind]
            if "workloads" not in m or cell.name in m["workloads"]]


def read_per_layer(cell: Cell, context: dict) -> dict:
    out = {}
    for m in metrics_for(cell, "per_layer"):
        base, _, variant = m["name"].partition(".")
        reader = importlib.import_module(f"metrics.{base}")
        value = reader.read(context, variant or None)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peak(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def use_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache/`` in the checkout.  Every program is kept, however
    fast it compiled, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def start_profiler(directory: Path) -> None:
    """Device ops and host spans; no Python tracer and no HLO protos,
    which would slow the host and bloat the trace."""
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(directory), profiler_options=options)


def find_trace(directory: Path) -> str:
    found = sorted(directory.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no trace under {directory}")
    return str(found[-1])


# ----------------------------------------------------------------- run --
def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_process: float,
        *, fault: str | None = None, log=print) -> dict:
    """One run of one cell on ``devices``; returns the result line's object."""
    import trace_reduce

    spans = Spans()
    trainer = make_trainer(cell, seed, devices, spans, traced=trace, fault=fault)
    used = list(devices)[:cell.chips]
    with spans.span("setup"):
        readings = program_readings(trainer, cell)
        for _ in range(len(cell.traffic.warmup) - COMPARED_STEPS):
            trainer.run(1)
        jax.block_until_ready(trainer.state)
    setup_s = time.perf_counter() - t_process
    log(f"set-up {setup_s:.3f} s; losses of the compared steps {readings['loss']}")

    context = {"spans": [], "trace": None}
    if trace:
        first = len(spans.records)
        start_profiler(TRACE_DIR)
        try:
            window = run_window(trainer, cell, spans, seconds=None,
                                max_steps=cell.traffic.trace_steps)
        finally:
            jax.profiler.stop_trace()
        context["spans"] = spans.records[first:]
    else:
        window = run_window(trainer, cell, spans, seconds=seconds)
    memory = memory_peak(used)
    log(f"memory stats of the first chip: {used[0].memory_stats()}")
    window_losses = [r.loss for r in trainer.history[window["first_step"]:]]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    log(f"window: {window['steps']} steps in {window['seconds']:.3f} s; "
        f"resizes {[(e['kind'], round(e['seconds'], 3)) for e in window['events']]}")
    log(f"host returned from each window step at {[round(x, 3) for x in window['step_ends']]} s")
    shapes = {k: v[0] for k, v in reference_module(cell).param_table(cell.model).items()}
    del trainer
    gc.collect()

    device = used[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(used), "memory_peak_bytes": memory}
    result = {"attempted": window["steps"], "failed": failed}
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(find_trace(TRACE_DIR)))
        context.update(
            trace=reduced, window=window, chips=len(used),
            step_flops=flops.step_flops(shapes, cell.model, cell.family,
                                        cell.traffic.batch, cell.traffic.seq),
            peak=peak(device.device_kind))
        result["metrics"] = read_per_layer(cell, context)
        if reduced:
            info["busy_s"] = reduced["busy_s"]
            info["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(end_to_end(cell, window), setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in metrics_for(cell, "end_to_end") if m["name"] in values}
    result["device"] = info

    t0 = time.perf_counter()
    ref = reference_readings(cell, seed, device=device)
    numbers = compare(readings, ref)
    log(f"reference: {time.perf_counter() - t0:.3f} s; losses {ref['loss']}; "
        f"worst leaves {numbers['worst']}; still leaves {numbers['still_leaves']}")
    ok, checks = judge(numbers, cell.limits)
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    return result
