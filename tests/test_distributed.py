"""Distributed substrate tests.  Multi-device cases run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test
process stays single-device per the dry-run contract)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import (compress, decompress,
                                           ef_transform,
                                           init_error_feedback)

REPO = os.path.join(os.path.dirname(__file__), "..")


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compression_roundtrip_and_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 1, (64, 64)).astype(np.float32))
    q, s = compress(g)
    assert q.dtype == jnp.int8
    err = float(jnp.max(jnp.abs(decompress(q, s) - g)))
    assert err <= float(s) * 0.5 + 1e-6
    # error feedback: residual carries the rounding error exactly
    ef = init_error_feedback({"w": g})
    (deq, ), _ = (None,), None
    newg, newef = ef_transform({"w": g}, ef)
    np.testing.assert_allclose(
        np.asarray(newg["w"] + newef["w"]), np.asarray(g), atol=1e-5)


@pytest.mark.slow          # 80 jitted train steps in a subprocess
def test_compressed_training_converges():
    """int8+EF training tracks uncompressed loss on a tiny model."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models.steps import build_model, init_train_state
    from repro.models.layers import softmax_xent
    from repro.optim import adamw_update
    from repro.distributed.compression import ef_transform, init_error_feedback
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), dtype="float32")
    model = build_model(cfg)
    def losses(compressed):
        params, opt = init_train_state(model, jax.random.PRNGKey(0))
        ef = init_error_feedback(params)
        rng = np.random.default_rng(0)
        # memorize one fixed batch: loss must drop
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)).astype(np.int32))
        labs = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)).astype(np.int32))
        ls = []
        @jax.jit
        def step(params, opt, ef):
            def lf(p):
                lg, _ = model.forward(p, toks)
                return softmax_xent(lg, labs)
            l, g = jax.value_and_grad(lf)(params)
            if compressed:
                g, ef = ef_transform(g, ef)
            params, opt = adamw_update(params, g, opt, 3e-3)
            return params, opt, ef, l
        for i in range(40):
            params, opt, ef, l = step(params, opt, ef)
            ls.append(float(l))
        return ls
    base = losses(False); comp = losses(True)
    print("BASE", base[0], base[-1], "COMP", comp[-1])
    assert comp[-1] < 0.7 * base[0], (comp[-1], base[0])    # it learns
    assert abs(comp[-1] - base[-1]) < 0.35 * abs(base[0])   # and tracks
    """
    out = _run_subprocess(code)
    assert "BASE" in out


def test_moe_a2a_matches_dense():
    """Expert-parallel all-to-all MoE == dense oracle on an 8-device mesh."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import moe as MOE
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    d, f, e, topk = 16, 32, 8, 2
    p = MOE.moe_init(jax.random.PRNGKey(0), d, f, e, jnp.float32, n_shared=1)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (4, 8, d)).astype(np.float32))
    y_dense, aux_d = MOE.moe_dense(p, x, topk)
    with jax.set_mesh(mesh):
        y_a2a, aux_a = MOE.moe_a2a(p, x, topk, cap_factor=4.0, mesh=mesh)
    err = float(jnp.max(jnp.abs(y_dense - y_a2a)))
    print("ERR", err, float(aux_d), float(aux_a))
    assert err < 2e-4, err
    assert abs(float(aux_d) - float(aux_a)) < 1e-4
    """
    out = _run_subprocess(code)
    assert "ERR" in out


def test_zero_sharding_specs():
    """ZeRO-1 adds a data-axis partition to optimizer state leaves."""
    code = """
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed.zero import opt_state_specs, zero_param_spec
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    # plain leaf: first divisible dim gets 'data'
    s = zero_param_spec(P(None, "model"), (8, 16), mesh)
    assert s == P("data", "model"), s
    # already-sharded dim combines axes when divisible
    s2 = zero_param_spec(P("model", None), (8, 3), mesh)
    assert s2 == P(("model", "data"), None), s2
    print("OK")
    """
    out = _run_subprocess(code)
    assert "OK" in out


@pytest.mark.slow          # granite-8b pjit on an 8-device mesh
def test_sharded_train_step_matches_single_device():
    """pjit on a 4x2 mesh == single-device math (same loss/params)."""
    code = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models.steps import build_model, init_train_state, make_train_step
    from repro.distributed import sharding as SH
    cfg = dataclasses.replace(get_smoke_config("granite-8b"), dtype="float32")
    model = build_model(cfg)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    ts = make_train_step(model, cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32)),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32))}
    p1, o1, m1 = jax.jit(ts)(params, opt, batch)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    with jax.set_mesh(mesh):
        psh = SH.param_shardings(mesh, params)
        bsh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
        f = jax.jit(ts, in_shardings=(psh, None, bsh))
        p2, o2, m2 = f(params, opt, batch)
    d = abs(float(m1["loss"]) - float(m2["loss"]))
    print("LOSSDIFF", d)
    assert d < 1e-4, d
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print("PDIFF", err)
    assert err < 1e-4, err
    """
    out = _run_subprocess(code)
    assert "LOSSDIFF" in out


def test_pipeline_parallel_equivalence():
    """GPipe shard_map schedule == sequential stage application."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_apply
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    rng = np.random.default_rng(0)
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    Ws = jnp.asarray(rng.normal(0, 0.5, (n_stages, d, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (n_micro, mb, d)).astype(np.float32))
    def stage_fn(w, h):
        return jnp.tanh(h @ w)
    with jax.set_mesh(mesh):
        y_pipe = pipeline_apply(stage_fn, Ws, x, mesh, axis="stage")
    y_seq = x
    for s in range(n_stages):
        y_seq = jax.vmap(lambda h: stage_fn(Ws[s], h))(y_seq)
    err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
    print("ERR", err)
    assert err < 1e-5, err
    """
    out = _run_subprocess(code)
    assert "ERR" in out


def test_moe_local_matches_dense_decode():
    """a2a-free local-experts decode path == dense oracle (kimi decode
    hillclimb, EXPERIMENTS.md §Perf C1)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import moe as MOE
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    d, f, e, topk = 16, 32, 8, 2
    p = MOE.moe_init(jax.random.PRNGKey(0), d, f, e, jnp.float32, n_shared=1)
    for b, t in [(4, 1), (8, 2)]:
        x = jnp.asarray(np.random.default_rng(b).normal(0, 1, (b, t, d))
                        .astype(np.float32))
        y_dense, _ = MOE.moe_dense(p, x, topk)
        with jax.set_mesh(mesh):
            y_loc, _ = MOE.moe_local(p, x, topk, cap_factor=4.0, mesh=mesh)
        err = float(jnp.max(jnp.abs(y_dense - y_loc)))
        assert err < 2e-4, (b, t, err)
    print("OK")
    """
    out = _run_subprocess(code)
    assert "OK" in out
