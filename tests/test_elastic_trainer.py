"""ElasticTrainer end-to-end on host devices (subprocess: needs >1 dev)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.configs import smoke_config
    from repro.elastic import DevicePool, ElasticTrainer, ElasticRuntime, SimulatedRMS
    from repro.elastic.rms import EventKind
    from repro.models import Model

    cfg = smoke_config("stablelm_3b")
    rt = ElasticRuntime(pool=DevicePool(), initial_nodes=1)
    rms = SimulatedRMS.scripted([
        (5, EventKind.GROW, 4),
        (10, EventKind.SHRINK, (2, 3)),
        (15, EventKind.FAIL, 1),
    ])
    tr = ElasticTrainer(model=Model(cfg), runtime=rt, rms=rms, batch=8, seq=32)
    hist = tr.run(20)
    assert len(hist) == 20
    nodes = [r.n_nodes for r in hist]
    assert nodes[4] == 1 and nodes[5] == 4, nodes
    assert nodes[10] == 2, nodes
    assert nodes[15] == 1, nodes
    losses = np.array(tr.losses())
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # redistribution happened on every reconfiguration
    assert len(tr.transfer_log) == 3
    assert all(t["bytes_total"] > 0 for t in tr.transfer_log)
    # reconfig history recorded TS for the shrink and the failure
    kinds = [(r.kind, r.mechanism) for r in rt.history]
    assert ("shrink", "termination_shrinkage") in kinds
    assert ("fail", "termination_shrinkage") in kinds
    print("ELASTIC_TRAINER_OK", losses[0], "->", losses[-1])
""")


@pytest.mark.slow
def test_elastic_trainer_event_loop():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=1200, env=env,
    )
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    assert "ELASTIC_TRAINER_OK" in proc.stdout


def test_from_scenario_names_the_chips_it_needs(monkeypatch):
    """On a host with fewer devices than the scenario's peak, the error
    says how many chips to run it on."""
    import jax

    from repro.configs import smoke_config
    from repro.elastic import ElasticTrainer
    from repro.malleability import get_scenario
    from repro.models import Model

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(ValueError, match="needs 4 chips"):
        ElasticTrainer.from_scenario(Model(smoke_config("xlstm_125m")),
                                     get_scenario("steady-cycle"))
