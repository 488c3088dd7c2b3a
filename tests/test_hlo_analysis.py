"""HLO static analyzer: trip-count-aware FLOP/byte/collective accounting
(the §Roofline engine) verified against constructed programs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import _shape_bytes, _split_args, analyze


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_scan_flops_are_trip_multiplied():
    def f(w, x):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    txt = _compile_text(
        f, jax.ShapeDtypeStruct((8, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((16, 64), jnp.float32))
    c = analyze(txt, 1)
    dot_flops = 2 * 16 * 64 * 64 * 8
    assert 0.9 * dot_flops <= c.flops <= 1.6 * dot_flops, c.flops
    # XLA's own cost_analysis undercounts by ~the layer count:
    xla = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((16, 64), jnp.float32)).compile()
    ca = xla.cost_analysis()   # dict (new jax) or list-of-dicts (old jax)
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    assert (ca or {}).get("flops", 0) < 0.3 * c.flops


def test_nested_scan_multiplicity():
    def f(x):
        def outer(c, _):
            def inner(d, _):
                return jnp.tanh(d @ d.T @ d), None
            d, _ = jax.lax.scan(inner, c, None, length=4)
            return d, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()

    txt = _compile_text(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    c = analyze(txt, 1)
    per_iter = 2 * 2 * 32 * 32 * 32      # two dots
    want = per_iter * 12                  # 3 x 4 iterations
    assert 0.9 * want <= c.flops <= 1.5 * want, (c.flops, want)


def test_shape_bytes():
    assert _shape_bytes("f32[4,8]{1,0}") == 128
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(f32[2,2], s32[4])") == 32
    assert _shape_bytes("pred[]") == 1


def test_split_args_nested():
    assert _split_args("%a, %b") == ["%a", "%b"]
    assert _split_args("f32[1,2]{1,0} %a, (s32[], f32[2]) %b") == \
        ["f32[1,2]{1,0} %a", "(s32[], f32[2]) %b"]


def test_dynamic_slice_counts_slice_not_buffer():
    """Per-iteration weight slices must not charge the stacked buffer."""
    def f(w, x):
        def body(c, i):
            wl = jax.lax.dynamic_index_in_dim(w, i, keepdims=False)
            return c * wl, None
        y, _ = jax.lax.scan(body, x, jnp.arange(64))
        return y.sum()

    txt = _compile_text(
        f, jax.ShapeDtypeStruct((64, 128, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32))
    c = analyze(txt, 1)
    full_buffer_per_iter = 64 * 128 * 128 * 4 * 64
    assert c.hbm_bytes < 0.5 * full_buffer_per_iter, c.hbm_bytes


def test_collectives_counted():
    import os
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import analyze
        mesh = make_mesh((8,), ("d",))
        def f(x):
            return (x @ x.T).sum()
        sh = NamedSharding(mesh, P(None, "d"))
        co = jax.jit(f, in_shardings=(sh,)).lower(
            jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
        c = analyze(co.as_text(), 8)
        assert c.total_collective_bytes > 0, c.collective_bytes
        print("COLL", c.collective_bytes)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLL" in out.stdout
