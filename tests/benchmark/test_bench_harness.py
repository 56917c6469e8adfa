"""CPU tests of the chip benchmark's harness: the manifest and what it
names, the traffic, FLOPs and peaks, the command's refusal without a TPU,
and the window loop at a smoke size."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import smoke_run  # noqa: F401  (puts benchmarks/chip on the path)
import flops
import harness
import traffic

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_keys_names_and_units():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p for p in MANIFEST["paths"])
    assert len(MANIFEST["command"]) <= 32 and all(_line(w) for w in MANIFEST["command"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = []
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
        names.append(c["name"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in [c["name"] for c in MANIFEST["configs"]]
        assert w["chips"] in (1, 4)
        names.append(w["name"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert (CHIP / "metrics" / f"{m['name'].partition('.')[0]}.py").is_file()
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = harness.load_cell(name, MANIFEST)
    assert cell.traffic.chips == cell.chips
    assert set(cell.limits) >= {"loss_gap", "grad_gap", "update_gap"}
    cfg = harness.program_config(cell)
    assert {k: getattr(cfg, k) for k in cell.model} == cell.model
    every = [m for m in harness.metrics_for(cell, "end_to_end")]
    assert {"setup_s", "train_tokens_per_s"} <= {m["name"] for m in every}
    assert harness.metrics_for(cell, "per_layer")


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no_such.cell", MANIFEST)


def _config_cell(config: str) -> harness.Cell:
    """A cell of ``config`` whether or not BENCHMARK.json runs it."""
    raw = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    return harness.Cell(name=config, chips=1, config=raw,
                        traffic=traffic.load("steady-1x4096"), limits={}, manifest=MANIFEST)


@pytest.mark.parametrize("config, params, matmul", [
    ("xlstm_125m", 172_929_792, 134_258_688),
    ("stablelm_3b-4l", 574_773_760, 445_972_480),
])
def test_reference_layout_is_the_programs_and_params_are_pinned(config, params, matmul):
    from reference import common
    from repro.models import Model

    cell = _config_cell(config)
    table = harness.reference_module(cell).param_table(cell.model)
    common.check_layout(table, Model(harness.program_config(cell)).abstract_params()[0])
    shapes = {k: v[0] for k, v in table.items()}
    assert flops.total_params(shapes) == params
    assert flops.matmul_params(shapes) == matmul


@pytest.mark.parametrize("config", ["xlstm_125m", "stablelm_3b-4l"])
def test_reference_is_the_programs_model_in_float32(config):
    """At the smoke size on the CPU, with the program computing in float32,
    the reference's loss and gradients are the program's."""
    import jax
    import jax.numpy as jnp
    from reference import common
    from repro.models import Model

    cell = harness.smoke_cell(_config_cell(config))
    model = Model(harness.program_config(cell).replace(dtype="float32",
                                                       logit_dtype="float32"))
    mod = harness.reference_module(cell)
    params = common.init_params(mod.param_table(cell.model), common.seed_key(2**33 + 5))
    batch = traffic.TokenStream(cell.model["vocab"], 4, 32, 7).sample(0)
    batch["labels"][1, :10] = -1
    tokens, labels = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])

    def ref_loss(p):
        total, count = mod.loss_sum(p, tokens, labels, cell.model, common.matmul("f32"))
        return total / count

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(params, batch)
        lr, gr = jax.value_and_grad(ref_loss)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for k in gp:
        np.testing.assert_allclose(gp[k], gr[k], rtol=1e-3, atol=1e-5 * float(jnp.max(jnp.abs(gr[k]))))


def test_step_flops_pinned():
    sl = harness.load_cell("stablelm_3b-4l.train-1chip", MANIFEST)
    shapes = {k: v[0] for k, v in harness.reference_module(sl).param_table(sl.model).items()}
    t = sl.traffic
    assert flops.step_flops(shapes, sl.model, sl.family, t.batch, t.seq) == (
        6 * 445_972_480 + 12 * 4 * 32 * 80 * 4096) * 4096
    xl = _config_cell("xlstm_125m")
    shapes = {k: v[0] for k, v in harness.reference_module(xl).param_table(xl.model).items()}
    assert flops.step_flops(shapes, xl.model, xl.family, 16, 2048) == 6 * 134_258_688 * 16 * 2048


def test_peaks_lookup():
    p = harness.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak("TPU v9 imaginary")


def test_resize_schedule():
    t = traffic.load("resize-1to4-4x1024")
    assert t.allocation(-1) == t.start == 1
    assert [t.allocation(s) for s in range(16)] == [4, 1, 1, 4] + [1] * 4 + [4] * 4 + [1] * 4
    assert t.resizes(13) == [(0, 1, 4), (1, 4, 1), (3, 1, 4), (4, 4, 1), (8, 1, 4), (12, 4, 1)]
    assert [s for s in range(4, 24) if t.opens_cycle(s)] == [4, 12, 20]
    steady = traffic.load("steady-1x4096")
    assert steady.resizes(100) == [] and all(steady.opens_cycle(s) for s in range(3, 9))


def test_token_stream_is_the_programs_and_seeded():
    from repro.configs import arch_config
    from repro.data import SyntheticTokens

    cfg = arch_config("xlstm_125m")
    seed = 2**33 + 5
    ours = traffic.TokenStream(cfg.vocab, 4, 64, seed).sample(3)
    theirs = SyntheticTokens(cfg, 4, 64, seed).sample(3)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(ours[k], theirs[k])
    again = traffic.TokenStream(cfg.vocab, 4, 64, seed).sample(3)
    np.testing.assert_array_equal(ours["tokens"], again["tokens"])
    other = traffic.TokenStream(cfg.vocab, 4, 64, seed + 1).sample(3)
    assert not np.array_equal(ours["tokens"], other["tokens"])


def test_metric_readers_find_nothing_in_an_empty_run():
    for m in MANIFEST["per_layer"]:
        base, _, variant = m["name"].partition(".")
        reader = __import__(f"metrics.{base}", fromlist=["read"])
        assert reader.read({"spans": [], "trace": None}, variant or None) is None


def _cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_command_refuses_without_a_tpu():
    cmd = MANIFEST["command"] + ["--workload", CELLS[0], "--seed", str(2**33),
                                 "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    p = subprocess.run(cmd, cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr and "platform 'cpu'" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in MANIFEST["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = MANIFEST["command"] + ["--workload", CELLS[0], "--seed", "1",
                                 "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_window_loop_at_smoke_size():
    r = smoke_run.run("stablelm_3b-4l.train-1chip")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["device"]["count"] == 1
    assert list(r)[-1] == "checks"


def test_resize_window_at_smoke_size_on_four_cpu_devices():
    p = subprocess.run([sys.executable, str(Path(smoke_run.__file__)),
                        "stablelm_3b-4l.resize-1to4", "--trace"],
                       cwd=ROOT, env=_cpu_env(4), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["attempted"] == 8 and r["device"]["count"] == 4
    assert set(r["metrics"]) == {f"{m}.{k}" for m in ("runtime_ms", "reshard_s", "first_step_s")
                                 for k in ("expand", "shrink")}
