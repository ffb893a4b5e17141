"""Trip-count-aware HLO analyzer: the roofline numbers ride on this."""

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.hlo_analysis import analyze_hlo


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flat_matmul_flops_exact():
    M, K, N = 128, 256, 64
    t = analyze_hlo(_hlo(lambda a, b: a @ b,
                         jnp.ones((M, K)), jnp.ones((K, N))))
    assert t.flops == 2 * M * N * K


def test_scan_multiplies_by_trip_count():
    M, K, n = 64, 128, 10

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    t = analyze_hlo(_hlo(f, jnp.ones((M, K)), jnp.ones((n, K, K))))
    assert t.flops == pytest.approx(n * 2 * M * K * K)


def test_nested_scans_multiply():
    M, K = 64, 128

    def f(x, ws):
        def outer(c, blk):
            return jax.lax.scan(lambda c2, w: (c2 @ w, None), c, blk)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    t = analyze_hlo(_hlo(f, jnp.ones((M, K)), jnp.ones((4, 5, K, K))))
    assert t.flops == pytest.approx(20 * 2 * M * K * K)


def test_remat_recompute_counted():
    M, K = 64, 128
    w1 = jnp.ones((K, K)) * 0.01
    w2 = jnp.ones((K, 1)) * 0.01

    def loss(x):
        h = jax.checkpoint(lambda x: jnp.tanh(x @ w1))(x)
        return jnp.sum(h @ w2)

    plain = analyze_hlo(_hlo(lambda x: jnp.sum(jnp.tanh(x @ w1) @ w2),
                             jnp.ones((M, K))))
    grad = analyze_hlo(_hlo(jax.grad(lambda x: loss(x)), jnp.ones((M, K))))
    # fwd + bwd at least doubles the dot flops (XLA may DCE the remat of a
    # single cheap op, so the recompute itself is not asserted here)
    assert grad.flops >= 2 * plain.flops - 1


def test_bytes_follow_xla_convention_on_matmul():
    M, K, N = 128, 256, 64
    t = analyze_hlo(_hlo(lambda a, b: a @ b,
                         jnp.ones((M, K)), jnp.ones((K, N))))
    expected = (M * K + K * N + 2 * M * N) * 4
    assert t.bytes == pytest.approx(expected, rel=0.3)


def test_elementwise_chains_are_fused_free():
    """A long elementwise chain should add ~no HBM traffic vs one op."""
    x = jnp.ones((256, 256))

    def chain(x):
        for _ in range(10):
            x = jnp.tanh(x) * 1.01 + 0.001
        return x

    t1 = analyze_hlo(_hlo(lambda x: jnp.tanh(x), x))
    t10 = analyze_hlo(_hlo(chain, x))
    assert t10.bytes <= t1.bytes * 6  # far less than 10 separate rw passes


def test_collective_bytes_under_spmd():
    import subprocess, sys, os, textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
        from repro.distributed.hlo_analysis import analyze_hlo
        mesh = jax.make_mesh((8,), ('model',), axis_types=(AxisType.Auto,))
        w_s = NamedSharding(mesh, P(None, 'model'))
        x_s = NamedSharding(mesh, P())
        def f(x, w):
            return jnp.sum(x @ w, axis=-1)   # contraction forces a psum-ish
        with jax.set_mesh(mesh):
            txt = jax.jit(f, in_shardings=(x_s, w_s)).lower(
                jax.ShapeDtypeStruct((64, 128), jnp.float32),
                jax.ShapeDtypeStruct((128, 512), jnp.float32),
            ).compile().as_text()
        t = analyze_hlo(txt)
        assert t.coll_bytes >= 0
        print('COLL', t.coll_bytes)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLL" in out.stdout
