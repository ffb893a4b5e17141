"""Distributed tests: run in subprocesses with 8 fake host devices.

Sharding decisions, pjit lowering of reduced configs per family, GPipe
pipeline, and elastic (re-mesh) checkpoint restore.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run8(code: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ sharding unit
def test_fit_spec_drops_nondividing_axes():
    from jax.sharding import PartitionSpec as P
    import jax
    from repro.launch.mesh import make_mesh_for
    from repro.launch.shapes import _fit_spec
    # single-device host: build an abstract mesh via make_mesh_for(1)
    mesh = make_mesh_for(1, model_axis=1)

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}

    s = _fit_spec(P("data", "model"), (32, 40), FakeMesh())
    assert s == P("data", None)
    s = _fit_spec(P(("pod", "data"), None), (64, 10), FakeMesh())
    assert s == P(("pod", "data"), None)
    s = _fit_spec(P(("pod", "data"), None), (16, 10), FakeMesh())
    assert s == P(None, None)


def test_shard_translates_embed_for_activations():
    from repro.distributed import sharding as sh
    rules = sh.default_rules()
    with sh.use_rules(rules):
        # no mesh: shard() is a no-op but must not raise
        import jax.numpy as jnp
        x = jnp.ones((2, 3, 4))
        y = sh.shard(x, "batch", "seq", "embed")
        assert y.shape == x.shape


def test_default_rules_multi_pod():
    from repro.distributed import sharding as sh
    r = sh.default_rules(multi_pod=True)
    assert r["batch"] == ("pod", "data")
    assert r["embed"] == ("pod", "data")
    assert r["heads"] == "model"


# ------------------------------------------------------- 8-device lowering
@pytest.mark.parametrize("arch,kind", [
    ("deepseek-7b", "train"),
    ("qwen3-moe-30b-a3b", "train"),
    ("rwkv6-1.6b", "decode"),
    ("hymba-1.5b", "prefill"),
    ("whisper-tiny", "train"),
    ("qwen2-vl-7b", "decode"),
])
def test_family_lowers_on_8dev_mesh(arch, kind):
    run8(f"""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import REGISTRY
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh_for
    from repro.launch.shapes import build_cell
    cfg = REGISTRY['{arch}'].reduced(n_layers=2, vocab=512)
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16)
    shape = ShapeSpec('t', '{kind}', 128, 16)
    mesh = make_mesh_for(8, model_axis=2)
    cell = build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           out_shardings=cell.out_shardings,
                           donate_argnums=cell.donate_argnums
                           ).lower(*cell.args).compile()
    assert compiled.cost_analysis()['flops'] > 0
    print('ok')
    """)


def test_train_step_executes_on_8dev_mesh():
    """Not just lowering: run 2 real sharded steps, loss decreases-ish."""
    run8("""
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs import REGISTRY
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh_for
    from repro.launch.shapes import build_cell
    from repro.models.model import build_model
    from repro.models.params import init_tree
    from repro.optim.adamw import AdamW
    from repro.data.pipeline import batches_for

    cfg = REGISTRY['deepseek-7b'].reduced(n_layers=2, vocab=512)
    shape = ShapeSpec('t', 'train', 64, 16)
    mesh = make_mesh_for(8, model_axis=2)
    cell = build_cell(cfg, shape, mesh)
    model = build_model(cfg)
    opt = AdamW()
    with jax.set_mesh(mesh):
        params = jax.device_put(
            init_tree(model.param_defs(), jax.random.PRNGKey(0)),
            cell.in_shardings[0])
        opt_state = jax.device_put(opt.init(params), cell.in_shardings[1])
        step = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings)
        stream = batches_for(cfg, shape)
        losses = []
        for i in range(3):
            batch = {k: jax.device_put(v, cell.in_shardings[2][k])
                     for k, v in next(stream).items()}
            loss, params, opt_state = step(params, opt_state, batch)
            losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    # 3 steps with warmup LR: executability + stability, not convergence
    assert abs(losses[-1] - losses[0]) < 0.5, losses
    print('losses', losses)
    """)


def test_pipeline_parallel_matches_sequential():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.distributed.pipeline import pipeline_apply
    mesh = jax.make_mesh((8,), ('pipe',), axis_types=(AxisType.Auto,))
    S, M, mb, d = 8, 4, 16, 32
    Ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    out = pipeline_apply(Ws, x, lambda W, h: jnp.tanh(h @ W), mesh, axis='pipe')
    ref = x
    for i in range(S):
        ref = jnp.tanh(ref @ Ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print('ok')
    """)


def test_elastic_restore_across_mesh_shapes():
    """Save sharded on a 4×2 mesh, restore onto 2×4 — logical layout."""
    run8("""
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.checkpoint.checkpointer import Checkpointer

    state = {'w': jnp.arange(64.0).reshape(8, 8)}
    with tempfile.TemporaryDirectory() as d:
        mesh1 = jax.make_mesh((4, 2), ('data', 'model'),
                              axis_types=(AxisType.Auto,) * 2)
        s1 = NamedSharding(mesh1, P('data', 'model'))
        sharded = jax.device_put(state['w'], s1)
        ck = Checkpointer(d)
        ck.save(5, {'w': sharded})
        mesh2 = jax.make_mesh((2, 4), ('data', 'model'),
                              axis_types=(AxisType.Auto,) * 2)
        s2 = NamedSharding(mesh2, P('data', 'model'))
        restored, manifest = ck.restore({'w': state['w']},
                                        shardings={'w': s2})
        assert manifest['step'] == 5
        np.testing.assert_array_equal(np.asarray(restored['w']), state['w'])
        assert restored['w'].sharding == s2
    print('ok')
    """)


def test_multipod_mesh_builders():
    run8("""
    # 8 host devices cannot build the 512-chip mesh, but the builder's
    # shape logic is checked via the abstract mesh (no device commit).
    from repro.launch.mesh import make_mesh_for
    m = make_mesh_for(8, model_axis=2)
    assert m.shape == {'data': 4, 'model': 2}
    print('ok')
    """)
