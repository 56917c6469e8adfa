"""ElasticTrainer: the full malleability loop as a library component.

Wraps a Model + ElasticRuntime + SimulatedRMS into one training loop:
every step it drains due RMS events, reconfigures (expand via the
parallel spawn plan, shrink/fail/straggler via TS), reshards the live
TrainState onto the rebuilt mesh (stage 3), takes the jitted step for
that mesh, and continues.
Mesh-independent checkpoints — periodic, or CHECKPOINT-event-driven —
cover the full-stop path: a RESTART event rebuilds the world at the
target size and reads the params back from the latest snapshot.

The trainer keeps one jitted train step per allocation, keyed on the
mesh (its devices in mesh order, its shape and axis names) and the
context's ``mode``.  A resize back to an allocation seen before takes
the stored step, which JAX dispatches without tracing, lowering or a
persistent-cache load; a mesh of the same size over other devices is
another key and compiles anew.  Entries hold executables, not state,
and live as long as the trainer.

With :mod:`repro.obs` enabled, each step is a ``run_step`` span holding
``drain``, ``mesh``, ``reshard`` (``.put``, with ``.gather`` inside it
when leaves are gathered on the old mesh, ``.account``, ``.wait``),
``rejit`` (counter ``rejit.cache_hits`` or ``rejit.cache_misses``),
``input``, ``dispatch`` and ``loss_sync``; JAX's compile events count
into whichever is open.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticTokens, make_batch_on_mesh
from repro.malleability.scenarios import RuntimeAdapter, dispatch_event
from repro.models import Model
from repro.parallel.sharding import ShardingContext
from repro.train.steps import (
    TrainState,
    build_init_fn,
    build_train_step,
    train_state_shardings,
)

from .reshard import device_put_tree, transfer_stats
from .rms import Event, EventKind, SimulatedRMS
from .runtime import ElasticRuntime

# The ``resize`` attribute of a step's ``run_step`` span, by the kind of
# the event applied before it.
_RESIZE = {EventKind.GROW: "expand", EventKind.SHRINK: "shrink"}


@dataclass
class StepRecord:
    step: int
    loss: float
    n_nodes: int


@dataclass
class ElasticTrainer:
    model: Model
    runtime: ElasticRuntime
    rms: SimulatedRMS
    lr: float = 1e-3
    batch: int = 8
    seq: int = 64
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    history: list[StepRecord] = field(default_factory=list)
    transfer_log: list[dict] = field(default_factory=list)

    def __post_init__(self):
        self._ctx = self._make_ctx()
        self._step_fn = None
        self._steps: dict[tuple, tuple] = {}
        self._restore_pending = False
        self._state: Optional[TrainState] = None
        self._data = SyntheticTokens(self.model.cfg, self.batch, self.seq, self.seed)
        self._ckpt = (
            CheckpointManager(self.checkpoint_dir) if self.checkpoint_dir else None
        )

    @classmethod
    def from_scenario(cls, model: Model, scenario, pool=None, engine=None,
                      **kwargs) -> "ElasticTrainer":
        """Build the full loop from a declarative scenario: the runtime
        executes the trace through the same ReconfigEngine the simulator
        charges, so per-event downtimes (and charged bytes) agree across
        both paths.  Pass ``engine`` to override the scenario's default —
        e.g. one carrying a :class:`~repro.elastic.reshard.PytreeBytesModel`
        so charged bytes exactly equal the measured reshard.

        Heterogeneous scenarios run too: the pool is partitioned with the
        scenario's uneven ``core_pool`` width vector (host devices must
        cover ``sum(core_pool)``)."""
        from repro.malleability.scenarios import check_scenario_pool, scenario_pool

        need = (sum(scenario.core_pool) if scenario.core_pool
                else scenario.max_nodes() * scenario.cores_per_node)
        if pool is None:
            devs = jax.devices()
            if len(devs) >= need:
                pool = scenario_pool(scenario, devices=devs)
        else:
            check_scenario_pool(scenario, pool)
        if pool is None or pool.n_nodes < scenario.max_nodes():
            width = (f"widths {scenario.core_pool}" if scenario.core_pool
                     else f"{scenario.cores_per_node} devices/node")
            have = (pool.n_nodes if pool is not None
                    else f"{len(jax.devices())} devices")
            raise ValueError(
                f"scenario {scenario.name!r} needs {need} chips: it peaks at "
                f"{scenario.max_nodes()} nodes ({width}), but the host/pool "
                f"only has {have}. Run it on a host with {need} chips "
                f"(`chip_smoke.py --chips 4` runs steady-cycle on four), or "
                f"on the CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                f"count={need} before importing jax; or pass a larger pool"
            )
        runtime = ElasticRuntime(
            pool=pool,
            initial_nodes=scenario.initial_nodes,
            engine=engine or scenario.default_engine(),
        )
        rms = SimulatedRMS.from_scenario(scenario)
        return cls(model=model, runtime=runtime, rms=rms, **kwargs)

    # ------------------------------------------------------------------ mesh --
    def _make_ctx(self) -> ShardingContext:
        return ShardingContext(mesh=self.runtime.mesh(("data",)), mode="train")

    def _rejit(self):
        """Point ``_step_fn`` at the jitted train step for the current
        mesh and return the ``TrainState`` shardings it takes.

        Steps are kept by ``(mesh, mode)``; ``Mesh`` equality is its
        devices in order, shape and axis names, which is what JAX's
        executable cache compares.  A miss builds and stores the step
        (``rejit.cache_misses``); a hit returns the stored one and its
        shardings without building (``rejit.cache_hits``).  ``model`` and
        ``lr`` are fixed for the trainer's life, so they are not in the
        key.
        """
        key = (self._ctx.mesh, self._ctx.mode)
        with obs.span("rejit"):
            entry = self._steps.get(key)
            if entry is None:
                obs.count("rejit.cache_misses")
                step_fn, shardings, _ = build_train_step(self.model, self._ctx, lr=self.lr)
                entry = self._steps[key] = (
                    jax.jit(
                        step_fn,
                        in_shardings=(shardings, None),
                        out_shardings=(shardings, None),
                        donate_argnums=(0,),
                    ),
                    shardings,
                )
            else:
                obs.count("rejit.cache_hits")
            self._step_fn, shardings = entry
        return shardings

    def _init_state(self):
        init_fn, _ = build_init_fn(self.model, self._ctx)
        self._state = init_fn(jax.random.key(self.seed))
        self._rejit()

    # --------------------------------------------------------------- resharding --
    def _reshard_state(self, step: int = -1, charged_bytes: int = 0):
        """Stage 3: move the live TrainState onto the rebuilt mesh.

        Logs the *measured* transfer stats next to the engine-*charged*
        bytes for the drained events: the parameter pytree's under
        ``bytes_*``, so the two accountings can be compared (they are
        equal when the engine uses a
        :class:`~repro.elastic.reshard.PytreeBytesModel` and one event
        was drained; multi-event drains reshard once over the net mesh
        change while the engine charges each hop), and the whole
        TrainState's, Adam's moments included, under ``state_bytes_*``.
        """
        with obs.span("reshard"):
            _, shardings = train_state_shardings(self.model, self._ctx)
            old = self._state
            with obs.span("reshard.put"):
                self._state = device_put_tree(self._state, shardings)
            with obs.span("reshard.account"):
                whole = self._log_transfer(old, step, charged_bytes)
            del old
            obs.count("reshard.bytes_moved", whole["bytes_moved"])
            obs.count("reshard.bytes_total", whole["bytes_total"])
            if obs.enabled():
                with obs.span("reshard.wait"):
                    jax.block_until_ready(self._state)
        self._rejit()

    def _log_transfer(self, old: TrainState, step: int, charged_bytes: int, **extra):
        stats = dict(transfer_stats(old.params, self._state.params))
        whole = transfer_stats(old, self._state)
        stats.update({f"state_{k}": v for k, v in whole.items()})
        stats.update(step=step, charged_bytes_moved=charged_bytes, **extra)
        self.transfer_log.append(stats)
        return whole

    def _restore_from_store(self, step: int, charged_bytes: int = 0):
        """SS-restart stage 3: params come back from the latest snapshot.

        Checkpoints are mesh-independent (host ``.npy`` leaves + a
        manifest), so a snapshot written under the old mesh restores
        under the rebuilt one's shardings.  Optimizer state and the step
        counter reshard live — mirroring what the saves persist.  With
        no store (or an empty one) the live state reshards instead: the
        charged cost story is identical, only the data source differs.
        """
        if self._ckpt is None or self._state is None:
            self._reshard_state(step=step, charged_bytes=charged_bytes)
            return
        _, shardings = train_state_shardings(self.model, self._ctx)
        spec_tree = jax.tree.map(lambda s: s.spec, shardings.params)
        tree, ck_step = self._ckpt.restore_latest(
            {"params": self._state.params}, mesh=self._ctx.mesh,
            spec_tree={"params": spec_tree},
        )
        if tree is None:
            self._reshard_state(step=step, charged_bytes=charged_bytes)
            return
        old = self._state
        self._state = device_put_tree(
            old._replace(params=tree["params"]), shardings,
        )
        self._log_transfer(old, step, charged_bytes, restored_from_step=ck_step)
        self._rejit()

    # -------------------------------------------------------------------- events --
    def _handle(self, ev: Event):
        """One RMS event through the SAME dispatch the scenario executors
        use — the mapping lives once, in repro.malleability.scenarios."""
        if ev.kind is EventKind.NOOP:
            return False
        applied = list(dispatch_event(
            RuntimeAdapter(self.runtime), ev.kind.value,
            nodes=ev.nodes, target_nodes=ev.target_nodes,
            queue_delay_s=ev.queue_delay_s,
        ))
        if ev.kind is EventKind.CHECKPOINT:
            # Persist the real snapshot next to the charged record, so a
            # later RESTART (or failure recovery) has bytes to read back.
            if self._ckpt is not None and self._state is not None:
                self._ckpt.save({"params": self._state.params},
                                len(self.history))
            return False  # no allocation change: keep the mesh and jit
        if ev.kind is EventKind.RESTART and applied:
            self._restore_pending = True
        return bool(applied)

    # ---------------------------------------------------------------------- run --
    def run(self, steps: int) -> list[StepRecord]:
        if self._state is None:
            self._init_state()
        for _ in range(steps):
            step_no = len(self.history)
            with obs.span("run_step", step=step_no):
                self._run_step(step_no)
        if self._ckpt:
            self._ckpt.wait()
        return self.history

    def _run_step(self, step_no: int):
        """Drain the events due, reconfigure if any applied, then train
        one step."""
        records_before = len(self.runtime.history)
        applied, resize = 0, None
        with obs.span("drain"):
            for ev in self.rms.events_until(step_no):
                if self._handle(ev):
                    applied += 1
                    resize = _RESIZE.get(ev.kind, resize)
            obs.annotate(events=applied)
        if applied:
            with obs.span("mesh"):
                self._ctx = self._make_ctx()
            charged = sum(
                r.bytes_moved
                for r in self.runtime.history[records_before:]
            )
            if self._restore_pending:
                self._restore_pending = False
                self._restore_from_store(step_no, charged_bytes=charged)
            else:
                self._reshard_state(step=step_no, charged_bytes=charged)
        obs.annotate(nodes=self.runtime.n_nodes)
        if resize:
            obs.annotate(resize=resize)
        with obs.span("input"):
            batch = make_batch_on_mesh(
                self._data.sample(step_no), self.model.cfg, self._ctx
            )
        with obs.span("dispatch"):
            self._state, metrics = self._step_fn(self._state, batch)
        with obs.span("loss_sync"):
            loss = float(metrics["loss"])
        self.history.append(
            StepRecord(step=step_no, loss=loss, n_nodes=self.runtime.n_nodes)
        )
        if self._ckpt and (step_no + 1) % self.checkpoint_every == 0:
            self._ckpt.save({"params": self._state.params}, step_no + 1)

    # ------------------------------------------------------------------ queries --
    @property
    def state(self) -> TrainState:
        return self._state

    def losses(self) -> list[float]:
        return [r.loss for r in self.history]
