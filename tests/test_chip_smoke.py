"""Rehearsals of ``chip_smoke.py`` without a chip: every phase at a tiny
size on the CPU (Pallas in interpret mode), the mesh phase on four
virtual CPU devices, and the refusals (no TPU; the script alone)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

from repro.core.passes.partition import PartitionConfig

REPO = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_script()

# Flickr's widths at 1% of its vertices and edges; a geometry small
# enough for several row blocks, so host streaming has shards to stream
# under a budget below the device-resident estimate.
TINY = dict(scale=0.01, geometry=PartitionConfig(n1=128, n2=32),
            interpret=True)


def _env(tmp_path, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.update(extra)
    return env


def test_every_phase_rehearses_on_cpu(capsys):
    chip_smoke.run(chip_smoke.SmokeConfig(**TINY))
    out = capsys.readouterr().out
    for phase in ("phase 2 b6", "phase 2 b2", "phase 3", "phase 4",
                  "phase 5"):
        assert f"[{phase}]" in out, phase
    assert "pallas->xla fallbacks 0" in out
    assert out.count("batch of 3") == 6


def test_mesh_phase_rehearses_on_four_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(SCRIPT)!r})
        import chip_smoke as cs
        from repro.core.passes.partition import PartitionConfig
        cs.run(cs.SmokeConfig(scale=0.01,
                              geometry=PartitionConfig(n1=128, n2=32)),
               n_devices=4)
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=_env(tmp_path, XLA_FLAGS=(
            "--xla_force_host_platform_device_count=4")))
    assert r.returncode == 0, r.stderr[-3000:]
    for d in range(4):
        assert f"device {d} (" in r.stdout
    assert "vs one-chip output" in r.stdout


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=300, env=_env(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_fails_without_the_repository(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=alone,
                       capture_output=True, text=True, timeout=300,
                       env=_env(tmp_path, PYTHONPATH=""))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
