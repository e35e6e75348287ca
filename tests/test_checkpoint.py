"""Checkpoint store: roundtrip, double-collect validation, elastic restore,
and the heartbeat straggler watchdog."""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.runtime import HeartbeatMonitor, fault_tolerance


def tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)},
            "step": jnp.int32(7)}


def test_roundtrip(tmp_path):
    t = tree()
    save_checkpoint(str(tmp_path), 3, t, version=1)
    assert latest_step(str(tmp_path)) == 3
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    out = restore_checkpoint(str(tmp_path), 3, sds)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_double_collect_retry_on_concurrent_writer(tmp_path):
    """A version bump between the two manifest reads forces a retry —
    the paper's SCAN/CMPTREE on files."""
    t = tree()
    save_checkpoint(str(tmp_path), 1, t, version=1)
    d = os.path.join(str(tmp_path), "step_00000001")
    manifest = json.load(open(os.path.join(d, "manifest.json")))

    calls = {"n": 0}
    orig_load = np.load

    def racy_load(path, *a, **k):
        calls["n"] += 1
        if calls["n"] == 1:   # concurrent writer commits mid-restore
            manifest["version"] = 2
            with open(os.path.join(d, "manifest.json"), "w") as f:
                json.dump(manifest, f)
        return orig_load(path, *a, **k)

    np.load = racy_load
    try:
        sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           t)
        out = restore_checkpoint(str(tmp_path), 1, sds)
    finally:
        np.load = orig_load
    # retried and succeeded against the new stable version
    assert calls["n"] > len(jax.tree.leaves(t))
    assert np.array_equal(np.asarray(out["a"]), np.asarray(t["a"]))


def test_elastic_reshard_restore(tmp_path):
    """Leaves are stored unsharded: restoring under a different device
    layout is just device_put with new shardings."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 1, t, version=1)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    out = restore_checkpoint(str(tmp_path), 1, sds, mesh=mesh,
                             specs={"w": P("data", None)})
    assert np.array_equal(np.asarray(out["w"]), np.arange(16.0).reshape(4, 4))
    assert out["w"].sharding.spec == P("data", None)


def test_heartbeat_straggler_detection(monkeypatch):
    """A fake clock makes every duration exact, so host load cannot turn
    an ordinary step into a straggler."""
    now = [0.0]
    monkeypatch.setattr(fault_tolerance.time, "perf_counter",
                        lambda: now[0])
    events = []
    mon = HeartbeatMonitor(window=16, factor=3.0,
                           on_straggler=lambda *a: events.append(a))
    for i in range(12):
        mon.start()
        now[0] += 0.002
        mon.stop(i)
    mon.start()
    now[0] += 0.05       # 25x median: a straggler
    mon.stop(99)
    assert mon.stragglers == 1
    assert events and events[0][0] == 99


def test_elastic_rescale_to_multidevice_mesh(tmp_path):
    """Train-state saved single-device restores sharded onto a 2x2 mesh —
    the elastic-scaling path (mesh size is not part of the format).
    Subprocess so the 4 placeholder devices never leak into other tests."""
    import subprocess
    import sys
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.checkpoint import save_checkpoint, restore_checkpoint

tree = {{"w": jnp.arange(64.0).reshape(8, 8),
         "m": jnp.ones((8, 8), jnp.float32)}}
save_checkpoint(r"{tmp_path}", 5, tree, version=1)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
out = restore_checkpoint(r"{tmp_path}", 5, sds, mesh=mesh,
                         specs={{"w": P("data", "model"), "m": P("data", None)}})
assert np.array_equal(np.asarray(out["w"]), np.arange(64.0).reshape(8, 8))
assert len(out["w"].sharding.device_set) == 4
# and it is usable under the mesh straight away
with mesh:
    y = jax.jit(lambda a, b: a @ b)(out["w"], out["m"])
assert np.isfinite(np.asarray(y)).all()
print("ELASTIC OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ELASTIC OK" in r.stdout
