"""Stage-3 resharding accounting: transfer_stats edge cases and the
predicted (devices_indices_map) twin that the cost simulator charges.

Multi-device cases run in a subprocess (the main test process must keep
seeing 1 device); the empty-tree edge cases run in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.elastic import predicted_transfer_stats, transfer_stats

ZEROS = {"bytes_total": 0, "bytes_stayed": 0, "bytes_moved": 0}


class TestEmptyTree:
    def test_transfer_stats_empty_tree(self):
        assert transfer_stats({}, {}) == ZEROS
        assert transfer_stats([], []) == ZEROS

    def test_predicted_transfer_stats_empty_tree(self):
        assert predicted_transfer_stats({}, {}, {}) == ZEROS


RESHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.elastic import predicted_transfer_stats, transfer_stats

    devs = jax.devices()

    def mesh(k):
        return Mesh(np.asarray(devs[:k], dtype=object).reshape((k,)), ("data",))

    def place(tree, shardings):
        return jax.device_put(tree, shardings)  # broadcasts a single sharding

    def check(label, tree, old_sh, new_sh):
        old = place(tree, old_sh)
        new = place(old, new_sh)
        measured = transfer_stats(old, new)
        predicted = predicted_transfer_stats(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree),
            old_sh, new_sh)
        assert measured == predicted, (label, measured, predicted)
        print("RESHARD_OK", label, measured["bytes_moved"], "moved")
        return measured

    tree = {
        "replicated": jnp.ones((16, 16), jnp.float32),   # 1024 B
        "sharded": jnp.ones((8, 4), jnp.float32),        # 128 B, split on dim 0
    }
    rep, shd = P(), P("data")

    def sh(k):
        m = mesh(k)
        return {"replicated": NamedSharding(m, rep),
                "sharded": NamedSharding(m, shd)}

    # grow-only: 2 -> 4 devices
    m = check("grow", tree, sh(2), sh(4))
    # replicated leaf ships one copy to each NEW device; sharded leaf's
    # bounds all change (8 rows: 4+4 -> 2+2+2+2), so it moves entirely.
    assert m["bytes_moved"] == 2 * 1024 + 128, m
    assert m["bytes_stayed"] == 2 * 1024, m

    # shrink-only: 4 -> 2 devices
    m = check("shrink", tree, sh(4), sh(2))
    # survivor replicas suffice; the sharded leaf rebalances entirely.
    assert m["bytes_moved"] == 128, m
    assert m["bytes_stayed"] == 2 * 1024, m

    # identity: nothing moves
    m = check("identity", tree, sh(4), sh(4))
    assert m["bytes_moved"] == 0, m

    # uneven shard counts: 3-way -> 2-way split of dim 6 (neither count
    # divides the other, so no shard bounds coincide and all bytes move).
    uneven = {"u": jnp.ones((6,), jnp.float32)}
    m3 = {"u": NamedSharding(mesh(3), P("data"))}
    m2 = {"u": NamedSharding(mesh(2), P("data"))}
    m = check("uneven", uneven, m3, m2)
    assert m["bytes_moved"] == m["bytes_total"] == 24, m

    # single-sharding broadcast form (one sharding for the whole tree)
    one = {"a": jnp.ones((4, 4), jnp.float32), "b": jnp.ones((2,), jnp.float32)}
    m = check("broadcast", one, NamedSharding(mesh(2), P()),
              NamedSharding(mesh(4), P()))
    assert m["bytes_moved"] == 2 * (64 + 8), m

    print("ALL_RESHARD_CASES_OK")
""")


@pytest.mark.slow
def test_predicted_equals_measured_across_reshards():
    """predicted_transfer_stats must equal transfer_stats byte-for-byte
    for grow-only, shrink-only, identity, uneven-shard, and broadcast
    sharding transitions (8 forced host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", RESHARD_SCRIPT], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    assert "ALL_RESHARD_CASES_OK" in proc.stdout


PUT_TREE_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import array as jax_array
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import obs
    from repro.elastic.reshard import device_put_tree

    devs = jax.devices()

    def mesh(ids):
        return Mesh(np.asarray([devs[i] for i in ids], dtype=object), ("data",))

    def shardings(m):
        return {"fsdp": NamedSharding(m, P("data")),
                "cols": NamedSharding(m, P(None, "data")),
                "replicated": NamedSharding(m, P()),
                "step": NamedSharding(m, P())}

    tree = {"fsdp": jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8) * 1.5,
            "cols": jnp.arange(4 * 8, dtype=jnp.float32).astype(jnp.bfloat16).reshape(4, 8),
            "replicated": jnp.linspace(-1, 1, 24, dtype=jnp.float32).reshape(4, 6),
            "step": jnp.asarray(7, jnp.int32)}
    sharded_bytes = tree["fsdp"].nbytes + tree["cols"].nbytes

    def host_read(self):
        raise AssertionError(f"leaf {self.shape} read through host memory")

    real_value = jax_array.ArrayImpl._value
    cases = {"4to1": ([0, 1, 2, 3], [0]), "4to2": ([0, 1, 2, 3], [0, 1]),
             "2to4": ([0, 1], [0, 1, 2, 3]), "1to4": ([0], [0, 1, 2, 3]),
             "4to4_elsewhere": ([0, 1, 2, 3], [4, 5, 6, 7])}
    out = {}
    for name, (src, dst) in cases.items():
        old = jax.device_put(tree, shardings(mesh(src)))
        jax.block_until_ready(old)
        target = shardings(mesh(dst))
        runs = []
        for _ in range(2):
            obs.reset()
            obs.enable()
            jax_array.ArrayImpl._value = property(host_read)
            try:
                with obs.span("put"):
                    new = device_put_tree(old, target)
                    jax.block_until_ready(new)
            finally:
                jax_array.ArrayImpl._value = real_value
                obs.disable()
            runs.append(obs.records())
        host_copies = [k for k, x in old.items() if x._npy_value is not None]
        plain = jax.device_put(old, target)
        same = {k: (new[k].sharding == plain[k].sharding
                    and new[k].sharding == target[k]
                    and np.asarray(new[k]).tobytes() == np.asarray(plain[k]).tobytes()
                    and np.asarray(new[k]).tobytes() == np.asarray(tree[k]).tobytes())
                for k in tree}
        first, second = runs
        out[name] = {
            "same": same, "host_copies": host_copies,
            "gathered": [r[0]["counters"]["reshard.gathered_bytes"] for r in runs],
            "gather_spans": [[x["name"] for x in r[1:]] for r in runs],
            "gather_compiles": [sum(x["counters"].get("compile.count", 0)
                                    for x in r if x["name"] == "reshard.gather")
                                for r in runs],
        }
    print(json.dumps({"cases": out, "sharded_bytes": sharded_bytes}))
""")


@pytest.fixture(scope="module")
def put_tree_cases():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", PUT_TREE_SCRIPT], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


GATHERS = {"4to1": True, "4to2": True, "2to4": True, "1to4": False,
           "4to4_elsewhere": False}


@pytest.mark.parametrize("case", sorted(GATHERS))
def test_device_put_tree_matches_device_put_without_host_copy(put_tree_cases, case):
    """device_put_tree gives plain device_put's values, bit for bit, and
    its shardings, with ArrayImpl._value patched to raise: no leaf,
    sharded, replicated or scalar, goes through host memory, and none of
    the old arrays is left holding a host copy."""
    got = put_tree_cases["cases"][case]
    assert got["same"] == {"fsdp": True, "cols": True, "replicated": True,
                           "step": True}
    assert got["host_copies"] == []


@pytest.mark.parametrize("case", sorted(GATHERS))
def test_device_put_tree_gathers_only_what_no_buffer_holds(put_tree_cases, case):
    """The two sharded leaves are gathered when some target shard is held
    whole by no old buffer (4->1, 4->2, 2->4), in one reshard.gather span
    whose programs compile on the first call only.  A move from one
    device, or onto the same layout on other devices, gathers nothing."""
    got = put_tree_cases["cases"][case]
    if GATHERS[case]:
        assert got["gathered"] == [put_tree_cases["sharded_bytes"]] * 2
        assert got["gather_spans"] == [["reshard.gather"]] * 2
        first, second = got["gather_compiles"]
        assert first >= 1 and second == 0
    else:
        assert got["gathered"] == [0, 0]
        assert got["gather_spans"] == [[], []]


CONCURRENT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.elastic.reshard import device_put_tree

    devs = jax.devices()

    def mesh(k):
        return Mesh(np.asarray(devs[:k]), ("data",))

    tree = {f"l{i}": jnp.arange(256 * 512, dtype=jnp.float32).reshape(256, 512) + i
            for i in range(60)}
    for rep in range(20):
        old = jax.device_put(tree, NamedSharding(mesh(8), P("data")))
        target = NamedSharding(mesh(2 if rep % 2 else 4), P(None, "data"))
        new = device_put_tree(old, {k: target for k in tree})
        jax.block_until_ready(new)
    assert all(np.array_equal(np.asarray(new[k]), np.asarray(tree[k])) for k in tree)
    print("GATHERS_DONE")
""")


@pytest.mark.slow
def test_many_gathers_complete_on_a_loaded_cpu():
    """Sixty leaves gathered 8 -> 4 and 8 -> 2 devices, twenty times, in
    four processes at once.  With every leaf's all-gather in flight
    together, XLA's CPU backend aborted most such processes on a
    collective's 40 s rendezvous timeout; one gather at a time, all
    finish."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", CONCURRENT_SCRIPT],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env) for _ in range(4)]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-3000:]
        assert "GATHERS_DONE" in out
