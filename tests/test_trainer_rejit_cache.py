"""ElasticTrainer keeps one jitted train step per allocation: a return to
a mesh it has compiled for takes the stored step and compiles nothing,
a mesh of the same size over other devices compiles anew, and the
stored step computes what a fresh jit computes."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# 1 -> 4 -> 1 -> 4 chips, then one chip on device 1 (the SHRINK keeps
# node 1), then a RESTART back onto all four.
SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from repro import obs
    from repro.configs import smoke_config
    from repro.elastic import DevicePool, ElasticRuntime, ElasticTrainer, SimulatedRMS
    from repro.elastic.rms import Event, EventKind
    from repro.models import Model

    def trainer(ckpt_dir):
        rms = SimulatedRMS(script=[
            Event(step=1, kind=EventKind.GROW, target_nodes=4),
            Event(step=2, kind=EventKind.SHRINK, nodes=(1, 2, 3)),
            Event(step=3, kind=EventKind.GROW, target_nodes=4),
            Event(step=4, kind=EventKind.SHRINK, nodes=(0, 2, 3)),
            Event(step=5, kind=EventKind.CHECKPOINT),
            Event(step=6, kind=EventKind.RESTART, target_nodes=4),
        ])
        return ElasticTrainer(model=Model(smoke_config("stablelm_3b")),
                              runtime=ElasticRuntime(pool=DevicePool(), initial_nodes=1),
                              rms=rms, batch=4, seq=16, checkpoint_dir=ckpt_dir,
                              checkpoint_every=1000)

    def devices(tr):
        return [[int(i) for i in m.device_ids.flat] for m, _mode in tr._steps]

    obs.enable()
    cached = trainer(sys.argv[1])
    cached.run(8)
    records = obs.records()
    obs.reset()

    class Forget(dict):
        def get(self, key, default=None):
            return default

    fresh = trainer(sys.argv[2])
    fresh._steps = Forget()
    fresh.run(8)
    print(json.dumps({"records": records, "fresh_records": obs.records(),
                      "nodes": [r.n_nodes for r in cached.history],
                      "devices": devices(cached),
                      "losses": cached.losses(), "fresh_losses": fresh.losses()}))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path_factory.mktemp("ckpt")),
                        str(tmp_path_factory.mktemp("ckpt_fresh"))],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _subtree(recs, root):
    out, todo = [], [root]
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(x for x in recs if x["parent"] == r["id"])
    return out


def _steps(recs):
    return {r["attrs"]["step"]: r for r in recs if r["name"] == "run_step"}


def _rejit(recs, step):
    """The counters of the ``rejit`` span under ``step``'s run_step."""
    (span,) = [x for x in _subtree(recs, _steps(recs)[step]) if x["name"] == "rejit"]
    return span["counters"]


def _compiles(spans):
    return {k: v for x in spans for k, v in x["counters"].items()
            if k.startswith("compile.")}


def test_first_visit_to_each_allocation_misses_and_compiles(runs):
    recs = runs["records"]
    assert runs["nodes"] == [1, 4, 1, 4, 1, 1, 4, 4]
    assert runs["devices"] == [[0], [0, 1, 2, 3], [1]]
    # The initial allocation is built before the first run_step.
    (init,) = [x for x in recs if x["name"] == "rejit" and x["parent"] is None]
    assert init["counters"]["rejit.cache_misses"] == 1
    assert "rejit.cache_hits" not in init["counters"]
    steps = _steps(recs)
    assert _compiles(_subtree(recs, steps[0]))["compile.count"] >= 1
    for step in (1, 4):   # 4 chips; one chip on device 1
        c = _rejit(recs, step)
        assert c["rejit.cache_misses"] == 1 and "rejit.cache_hits" not in c, step
        assert _compiles(_subtree(recs, steps[step]))["compile.count"] >= 1, step


def test_return_visits_hit_and_compile_nothing(runs):
    recs = runs["records"]
    steps = _steps(recs)
    for step in (2, 3, 6):   # shrink to device 0, expand, RESTART onto four
        c = _rejit(recs, step)
        assert c.get("rejit.cache_hits") == 1 and "rejit.cache_misses" not in c, step
        jit_layer = [x for x in _subtree(recs, steps[step])
                     if x["name"] in ("rejit", "dispatch", "loss_sync")]
        assert len(jit_layer) == 3 and _compiles(jit_layer) == {}, step
    # The second expansion has compiled every program it runs, the
    # reshard's too; the reshard only traces the state's shapes again.
    assert [k for k in _compiles(_subtree(recs, steps[3]))
            if not k.startswith("compile.trace_s:")] == []
    hits = sum(x["counters"].get("rejit.cache_hits", 0) for x in recs)
    misses = sum(x["counters"].get("rejit.cache_misses", 0) for x in recs)
    assert (hits, misses) == (3, 3)


def test_same_size_mesh_over_other_devices_misses(runs):
    recs = runs["records"]
    steps = _steps(recs)
    # Steps 2 and 4 both run on one chip: device 0, then device 1.
    assert steps[2]["attrs"]["nodes"] == steps[4]["attrs"]["nodes"] == 1
    assert _rejit(recs, 2) == {"rejit.cache_hits": 1}
    assert _rejit(recs, 4)["rejit.cache_misses"] == 1
    dispatch = next(x for x in _subtree(recs, steps[4]) if x["name"] == "dispatch")
    assert dispatch["counters"]["compile.count"] >= 1


def test_cached_steps_give_the_losses_of_fresh_jits(runs):
    """A trainer whose lookup always misses re-jits on every resize and
    trains to bit-identical losses."""
    fresh = runs["fresh_records"]
    assert sum(x["counters"].get("rejit.cache_misses", 0) for x in fresh) == 6
    assert not any("rejit.cache_hits" in x["counters"] for x in fresh)
    assert runs["losses"] == runs["fresh_losses"]
    assert len(runs["losses"]) == 8
