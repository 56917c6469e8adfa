"""repro.obs: spans and compile counters, off by default, and the spans
ElasticTrainer opens around each stage of a resize."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def tracing():
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


@pytest.fixture
def listener_calls(monkeypatch):
    """Every call into jax.monitoring's listener registry, passed through."""
    calls = []
    for name in ("register_event_listener", "register_event_time_span_listener",
                 "unregister_event_listener", "unregister_event_time_span_listener"):
        real = getattr(jax.monitoring, name)

        def spy(cb, _name=name, _real=real):
            calls.append(_name)
            return _real(cb)

        monkeypatch.setattr(jax.monitoring, name, spy)
    return calls


def _children(recs, parent):
    return [r for r in recs if r["parent"] == parent["id"]]


def _subtree(recs, root):
    out, todo = [], [root]
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(_children(recs, r))
    return out


def test_off_records_nothing_and_registers_no_listener(monkeypatch, listener_calls):
    assert not obs.enabled()
    obs.reset()

    def forbidden(*a, **k):
        raise AssertionError("touched while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(obs.time, "perf_counter_ns", forbidden)
    first = obs.span("run_step", step=0)
    with first:
        with obs.span("dispatch") as inner:
            assert inner is None
            obs.count("compile.count", 1)
            obs.annotate(resize="expand")
            jax.jit(lambda x: x * 3 - 1)(jnp.ones(3)).block_until_ready()
    assert obs.span("other") is first
    assert obs.records() == []
    assert listener_calls == []


def test_enable_and_disable_register_and_unregister_listeners(listener_calls):
    obs.enable()
    obs.enable()
    assert obs.enabled()
    obs.disable()
    obs.disable()
    assert not obs.enabled()
    assert sorted(listener_calls) == sorted([
        "register_event_listener", "register_event_time_span_listener",
        "unregister_event_listener", "unregister_event_time_span_listener"])


def test_spans_nest_with_parent_ids(tracing):
    with obs.span("run_step", step=7):
        with obs.span("reshard"):
            with obs.span("reshard.put"):
                pass
            with obs.span("reshard.wait"):
                pass
        with obs.span("dispatch"):
            pass
    with obs.span("run_step", step=8):
        pass
    recs = obs.records()
    by = {r["name"] + str(r["attrs"].get("step", "")): r for r in recs}
    assert [r["name"] for r in recs] == ["run_step", "reshard", "reshard.put",
                                         "reshard.wait", "dispatch", "run_step"]
    assert [r["id"] for r in recs] == list(range(6))
    assert by["run_step7"]["parent"] is None and by["run_step8"]["parent"] is None
    assert by["reshard"]["parent"] == by["run_step7"]["id"]
    assert by["reshard.put"]["parent"] == by["reshard"]["id"]
    assert by["reshard.wait"]["parent"] == by["reshard"]["id"]
    assert by["dispatch"]["parent"] == by["run_step7"]["id"]
    for r in recs:
        assert isinstance(r["t0"], int) and r["t0"] <= r["t1"]
    outer = by["run_step7"]
    assert all(outer["t0"] <= r["t0"] and r["t1"] <= outer["t1"]
               for r in _subtree(recs, outer))
    assert by["run_step7"]["attrs"] == {"step": 7}


def test_count_and_annotate_go_to_the_innermost_span(tracing):
    obs.count("dropped", 1)   # no span open
    with obs.span("reshard"):
        obs.count("reshard.bytes_moved", 10)
        with obs.span("reshard.put"):
            obs.count("n")
            obs.count("n", 2)
            obs.annotate(kind="shrink")
        obs.count("reshard.bytes_moved", 5)
    outer, inner = obs.records()
    assert outer["counters"] == {"reshard.bytes_moved": 15}
    assert inner["counters"] == {"n": 3}
    assert inner["attrs"] == {"kind": "shrink"} and outer["attrs"] == {}


def test_a_fresh_jit_counts_one_compile_and_its_second_call_none(tracing):
    x = jnp.arange(5.0)
    x.block_until_ready()
    f = jax.jit(lambda v: jnp.sin(v) * 2 + 1)
    with obs.span("first"):
        f(x).block_until_ready()
    with obs.span("second"):
        f(x).block_until_ready()
    first, second = obs.records()
    c = first["counters"]
    assert c["compile.count"] == 1
    assert c["compile.backend_s:jit(<lambda>)"] > 0
    span_s = (first["t1"] - first["t0"]) * 1e-9
    # Nested traces (jnp functions traced inside the lambda) are not
    # counted twice: all the compile seconds fit inside the span.
    secs = {kind: [v for k, v in c.items() if k.startswith(f"compile.{kind}:")]
            for kind in ("trace_s", "lower_s", "backend_s")}
    assert all(v and min(v) >= 0 for v in secs.values()), c
    assert sum(sum(v) for v in secs.values()) <= span_s
    assert not any(k.startswith("compile.") for k in second["counters"])


SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro import obs
    from repro.configs import smoke_config
    from repro.elastic import DevicePool, ElasticRuntime, ElasticTrainer, SimulatedRMS
    from repro.elastic.rms import Event, EventKind
    from repro.models import Model

    rms = SimulatedRMS(script=[
        Event(step=2, kind=EventKind.GROW, target_nodes=4),
        Event(step=4, kind=EventKind.SHRINK, nodes=(1, 2, 3)),
        Event(step=5, kind=EventKind.CHECKPOINT),
        Event(step=6, kind=EventKind.RESTART, target_nodes=2),
    ])
    tr = ElasticTrainer(model=Model(smoke_config("stablelm_3b")),
                        runtime=ElasticRuntime(pool=DevicePool(), initial_nodes=1),
                        rms=rms, batch=4, seq=16, checkpoint_dir=sys.argv[1],
                        checkpoint_every=1000)
    tr.run(2)
    off = obs.records()
    obs.enable()
    tr.run(5)
    obs.disable()
    whole = sum(x.nbytes for x in jax.tree.leaves(tr.state))
    print(json.dumps({"off": off, "records": obs.records(), "whole": whole,
                      "log": tr.transfer_log,
                      "nodes": [r.n_nodes for r in tr.history]}))
""")


@pytest.fixture(scope="module")
def traced_resizes(tmp_path_factory):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path_factory.mktemp("ckpt"))],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_trainer_spans_each_resize_stage(traced_resizes):
    r = traced_resizes
    assert r["off"] == []
    assert r["nodes"] == [1, 1, 4, 4, 1, 1, 2]
    recs = r["records"]
    steps = [x for x in recs if x["name"] == "run_step"]
    assert [s["attrs"]["step"] for s in steps] == [2, 3, 4, 5, 6]
    assert [s["attrs"]["nodes"] for s in steps] == [4, 4, 1, 1, 2]
    assert [s["attrs"].get("resize") for s in steps] == ["expand", None, "shrink", None, None]
    for s in steps:
        names = [c["name"] for c in _children(recs, s)]
        assert names[0] == "drain" and names[-3:] == ["input", "dispatch", "loss_sync"]
    drains = [c for s in steps for c in _children(recs, s) if c["name"] == "drain"]
    assert [d["attrs"]["events"] for d in drains] == [1, 0, 1, 0, 1]

    by_kind = {s["attrs"]["resize"]: s for s in steps if "resize" in s["attrs"]}
    for kind, s in by_kind.items():
        sub = _subtree(recs, s)
        names = {x["name"] for x in sub}
        assert {"mesh", "reshard", "reshard.put", "reshard.account", "reshard.wait",
                "rejit", "dispatch"} <= names, (kind, names)
        reshard = next(x for x in sub if x["name"] == "reshard")
        assert [c["name"] for c in _children(recs, reshard)] == [
            "reshard.put", "reshard.account", "reshard.wait"]
        dispatch = next(x for x in sub if x["name"] == "dispatch")
        assert dispatch["counters"]["compile.count"] >= 1, kind
        assert sum(x["counters"].get("compile.count", 0) for x in sub) >= 1
        assert reshard["counters"]["reshard.bytes_moved"] > 0

    # A step on an allocation already compiled for compiles nothing.
    steady = next(s for s in steps if s["attrs"]["step"] == 3)
    assert not any(k.startswith("compile.") for x in _subtree(recs, steady)
                   for k in x["counters"])


def test_transfer_log_counts_the_whole_train_state(traced_resizes):
    r = traced_resizes
    log = r["log"]
    assert [e["step"] for e in log] == [2, 4, 6]
    recs = r["records"]
    reshards = [x for x in recs if x["name"] == "reshard"]
    for entry, span in zip(log, reshards):
        assert span["counters"]["reshard.bytes_total"] == entry["state_bytes_total"]
        assert span["counters"]["reshard.bytes_moved"] == entry["state_bytes_moved"]
    for e in log:
        assert e["state_bytes_total"] == e["state_bytes_stayed"] + e["state_bytes_moved"]
        # params, Adam's two moments and two step counters
        assert e["state_bytes_total"] > 3 * e["bytes_total"]
    expand, shrink, restore = log
    # Onto one chip the state lands once: the whole TrainState's bytes.
    assert shrink["state_bytes_total"] == r["whole"]
    assert expand["state_bytes_total"] >= r["whole"]
    assert restore["restored_from_step"] == 5
    assert "state_bytes_moved" in restore
