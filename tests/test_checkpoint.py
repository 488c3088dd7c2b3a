"""Checkpoint: atomic save/restore, resume, elastic re-mesh, crash safety."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save

REPO = os.path.join(os.path.dirname(__file__), "..")


def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.normal(0, 1, (4, 8)).astype(np.float32)),
            "b": {"c": jnp.asarray(r.integers(0, 9, (3,)).astype(np.int32)),
                  "d": [jnp.ones((2, 2), jnp.bfloat16)] * 2}}


def test_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t, meta={"x": 1})
    t2, step, meta = restore(str(tmp_path), t)
    assert step == 7 and meta == {"x": 1}
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_latest_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, t, keep=2)
    assert latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000004", "step_00000005"]


def test_incomplete_checkpoint_ignored(tmp_path):
    t = _tree()
    save(str(tmp_path), 1, t)
    # simulate a crash mid-save: manifest without the complete flag
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text(json.dumps({"step": 2}))
    assert latest_step(str(tmp_path)) == 1


def test_elastic_restore_resharded(tmp_path):
    """Saved unsharded; restored with an explicit 2x4 mesh sharding."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.checkpoint import save, restore
t = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
save({str(tmp_path)!r}, 3, t)
mesh = make_mesh((2, 4), ("data", "model"))
sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
t2, step, _ = restore({str(tmp_path)!r}, t, shardings=sh)
assert step == 3
assert t2["w"].sharding == sh["w"]
np.testing.assert_array_equal(np.asarray(t2["w"]), np.asarray(t["w"]))
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow          # two full training subprocesses
def test_crash_restart_loss_continuity(tmp_path):
    """launch.train: crash at step 12, relaunch with --resume auto; the
    run completes and the data stream stays deterministic."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "qwen3-0.6b", "--smoke", "--steps", "20", "--batch", "2",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every",
            "5", "--log-every", "5", "--resume", "auto"]
    r1 = subprocess.run(args + ["--crash-at", "12"], env=env,
                        capture_output=True, text=True, timeout=600)
    assert r1.returncode == 42  # simulated failure
    assert latest_step(str(tmp_path)) == 10
    r2 = subprocess.run(args, env=env, capture_output=True, text=True,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[resume] restored step 10" in r2.stdout
    assert "done: 20 steps" in r2.stdout
