"""Kernel-granular tuning plane: catalog, compilettes, coordinator handles.

Control-loop tests run deterministically on the ``VirtualClock`` with the
catalog's *virtual* backend (variants priced by the analytical cost
models, compile cost declared); the catalog/AOT tests build and run the
real (interpret-mode) kernels at tiny shapes.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Param,
    RegenerationPolicy,
    TPU_V5E,
    VirtualClock,
    VirtualClockEvaluator,
    product_space,
    virtual_compilette,
    virtual_kernel,
)
from repro.kernels import KernelCompilette, KernelDef, get_catalog
from repro.runtime.coordinator import TuningCoordinator
from repro.runtime.kernel_plane import (
    KernelTuningPlane,
    active_plane,
    parse_kernel_strategies,
    use_kernel_plane,
)
from repro.runtime.lifecycle import TunerLifecycle, TunerState

# Shapes at which every kernel has a rich valid space (virtual tests).
SPECS = {
    "matmul": {"M": 512, "N": 512, "K": 512, "dtype": "float32"},
    "attention": {"B": 4, "Tq": 512, "Tkv": 512, "H": 8, "Hk": 4,
                  "Dh": 64, "causal": True, "dtype": "float32"},
    "rmsnorm": {"N": 2048, "d": 512, "dtype": "float32"},
}

GEN_COST = 0.002


def first_valid(comp):
    return next(iter(comp.space.iter_valid()))


def make_virtual_plane(clock, coord, **kw):
    return KernelTuningPlane(
        coord, virtual=(clock, TPU_V5E), gen_cost_s=GEN_COST,
        evaluator_factory=lambda c: VirtualClockEvaluator(clock), **kw)


# ---------------------------------------------------------------- catalog
def test_catalog_discovers_every_ops_compilette():
    """Every kernels/*/ops.py module must expose a registered KERNEL."""
    import repro.kernels as pkg

    expected = set()
    for root in pkg.__path__:
        for entry in pathlib.Path(root).iterdir():
            if (entry / "ops.py").is_file():
                expected.add(entry.name)
    assert expected, "kernel packages vanished?"
    cat = get_catalog()
    assert set(cat.names()) == expected
    for name in expected:
        defn = cat.get(name)
        assert isinstance(defn, KernelDef) and defn.name == name


@pytest.mark.parametrize("name,spec", [
    ("matmul", {"M": 64, "N": 128, "K": 128, "dtype": "float32"}),
    ("attention", {"B": 1, "Tq": 16, "Tkv": 16, "H": 2, "Hk": 1, "Dh": 8,
                   "causal": True, "dtype": "float32"}),
    ("rmsnorm", {"N": 16, "d": 8, "dtype": "float32"}),
    ("lintra", {"H": 8, "W": 16, "bands": 3, "dtype": "float32"}),
    ("euclid", {"N": 128, "M": 64, "D": 32, "dtype": "float32"}),
])
def test_kernel_compilette_builds_and_runs(name, spec):
    """Real backend: generate a variant, run it on example args."""
    comp = get_catalog().compilette(name, spec)
    assert isinstance(comp, KernelCompilette)
    kern = comp.generate(first_valid(comp))
    out = kern.fn(*comp.example_call_args())
    assert np.all(np.isfinite(np.asarray(out, dtype=np.float32)))
    assert kern.generation_time_s > 0


def test_refused_compile_is_a_quarantined_generation_failure():
    """A variant the backend refuses to compile (a Mosaic VMEM or tiling
    limit on the chip) raises out of generation: there is no lazy
    fallback to hide it, so the tuner records the hole and quarantines
    the point."""
    from repro.core import Evaluator, OnlineAutotuner

    def generate(point, spec, *, interpret=None):
        @jax.jit
        def fn(x):
            if point["v"] == 2:
                raise ValueError("refused: block exceeds the VMEM limit")
            return x * point["v"]
        return fn

    defn = KernelDef(
        name="refusable",
        make_space=lambda spec: product_space(
            [Param("v", (1, 2), phase=1)]),
        generate=generate,
        extract_spec=lambda x: {"N": int(x.shape[0])},
        abstract_args=lambda spec: (
            jax.ShapeDtypeStruct((spec["N"],), jnp.float32),),
        example_args=lambda spec: (jnp.ones((spec["N"],), jnp.float32),))
    comp = KernelCompilette(defn, {"N": 8})
    with pytest.raises(ValueError, match="refused"):
        comp.generate({"v": 2})
    tuner = OnlineAutotuner(
        comp, Evaluator(mode="real", real_runs=1, warmup=0,
                        make_args=comp.example_call_args),
        policy=RegenerationPolicy(max_overhead_frac=1e9),
        wake_every=None)
    while not tuner.explorer.finished:
        tuner.wake()
    assert comp.aot_compiles == 2          # the reference and v=1
    assert tuner.accounts.quarantined == 1
    assert tuner.explorer.is_quarantined({"v": 2})
    assert tuner.best_point == {"v": 1}


def test_extract_spec_roundtrip():
    """spec → example args → extract_spec is the identity (handles key
    on specs extracted from live arguments)."""
    cat = get_catalog()
    for name, spec in SPECS.items():
        comp = cat.compilette(name, spec)
        extracted = cat.spec_of(name, *comp.example_call_args())
        for k, v in spec.items():
            assert extracted[k] == v, (name, k)


def test_aot_compile_cost_lands_in_generation_time():
    """Satellite: `jit(f).lower(...).compile()` runs inside _generate, so
    the real XLA compile is measured into generation_time_s (charged to
    gen_spent_s) instead of polluting the first evaluation."""
    cat = get_catalog()
    spec = {"N": 64, "d": 32, "dtype": "float32"}
    comp = cat.compilette("rmsnorm", spec, aot=True)
    pt = first_valid(comp)
    kern = comp.generate(pt)
    assert comp.aot_compiles == 1
    assert kern.generation_time_s > 0
    x, w = comp.example_call_args()
    from repro.kernels.rmsnorm.ops import rmsnorm_ref
    np.testing.assert_allclose(kern.fn(x, w), rmsnorm_ref(x, w),
                               rtol=1e-5, atol=1e-5)
    # lazy mode keeps the pre-PR-4 behaviour
    lazy = cat.compilette("rmsnorm", spec, aot=False)
    kern2 = lazy.generate(pt)
    assert lazy.aot_compiles == 0
    np.testing.assert_allclose(kern2.fn(x, w), rmsnorm_ref(x, w),
                               rtol=1e-5, atol=1e-5)


def test_virtual_backend_prices_by_cost_model():
    clock = VirtualClock()
    comp = get_catalog().compilette(
        "matmul", SPECS["matmul"], virtual=(clock, TPU_V5E),
        gen_cost_s=GEN_COST)
    pt = first_valid(comp)
    kern = comp.generate(pt)
    assert kern.meta["simulated"] and kern.generation_time_s == GEN_COST
    expected = comp.simulate(pt, TPU_V5E)
    assert kern.fn.score_s == pytest.approx(expected)
    t0 = clock()
    kern.fn()
    assert clock() - t0 == pytest.approx(expected)


def test_untunable_spec_is_skippable_not_fatal():
    """A spec at which every point is a hole (tiny euclid) registers as
    None with require=False and raises loudly with require=True."""
    clock = VirtualClock()
    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:v", clock=clock)
    plane = make_virtual_plane(clock, coord)
    dead = {"N": 16, "M": 8, "D": 4, "dtype": "float32"}
    assert plane.register_spec("euclid", dead, require=False) is None
    with pytest.raises(ValueError):
        plane.register_spec("euclid", dead)
    assert coord.stats()["n_kernels"] == 0


# ------------------------------------------------------------- acceptance
def test_kernel_plane_virtual_acceptance():
    """Acceptance: with kernel-granular tuning, matmul/attention/rmsnorm
    each register as an independent coordinator-managed compilette with
    its own strategy and registry key, and stats() reports per-kernel
    gen/stall/eval accounting that sums consistently into the aggregate
    — all deterministic under the VirtualClock."""
    clock = VirtualClock()
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock,
        async_generation=True, prefetch=1)
    plane = make_virtual_plane(
        clock, coord,
        strategies={"matmul": "greedy", "attention": "random"})
    handles = {n: plane.register_spec(n, s) for n, s in SPECS.items()}
    assert all(h is not None for h in handles.values())
    for i in range(3000):
        for h in handles.values():
            h(i)
        coord.maybe_pump()
        if all(h.tuner.explorer.finished for h in handles.values()):
            break
    s = coord.stats()
    assert s["n_kernels"] == 3
    assert set(s["kernels"]) == {"matmul", "attention", "rmsnorm"}
    # per-kernel strategies took effect
    assert s["kernels"]["matmul"]["strategy"] == "greedy"
    assert s["kernels"]["attention"]["strategy"] == "random"
    assert s["kernels"]["rmsnorm"]["strategy"] == "two_phase"
    # independent registry keys: one tuned entry per (kernel, spec),
    # persisted under the source-hashed device fingerprint
    for m in coord._managed:
        coord._flush_best(m)
    by_name = {m.name: m for m in coord._managed}
    for name, spec in SPECS.items():
        dev = by_name[name].registry_device
        assert dev.startswith("test:v:src-"), name
        assert coord.registry.get(name, spec, dev) is not None, name
    # every kernel explored and was billed for generation
    for name, k in s["kernels"].items():
        assert k["regenerations"] > 0, name
        assert k["gen_spent_s"] > 0, name
    # double-buffered pipeline: the budget paid, the hot path never did
    assert s["gen_spent_s"] > 0 and s["gen_stall_s"] == 0.0
    # per-kernel accounting sums consistently into the aggregate
    for f in ("gen_spent_s", "gen_stall_s", "eval_spent_s"):
        rollup = (sum(k[f] for k in s["kernels"].values())
                  + s["retired_accounts"][f])
        assert rollup == pytest.approx(s[f]), f


def test_kernel_plane_shares_budget_with_step_program():
    """Satellite: two catalog kernels + one whole-step-program compilette
    under ONE shared budget — fairness gives every unit slots, the total
    stays within the cap, and a retired unit's accounting survives in
    the tombstone."""
    clock = VirtualClock()
    coord = TuningCoordinator(
        policy=RegenerationPolicy(max_overhead_frac=0.2, invest_frac=0.5),
        device="test:v", clock=clock, async_generation=True,
        lifecycle=TunerLifecycle(seq_buckets=True, idle_evict_s=0.05))
    plane = make_virtual_plane(clock, coord)
    k1 = plane.register_spec("matmul", SPECS["matmul"])
    k2 = plane.register_spec("rmsnorm", SPECS["rmsnorm"])
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1)])
    step = coord.register(
        "step_program",
        virtual_compilette(clock, "step_program", sp,
                           lambda p: 0.008 / p["unroll"],
                           gen_cost_s=GEN_COST),
        VirtualClockEvaluator(clock),
        reference_fn=virtual_kernel(clock, 0.008))
    for i in range(3000):
        k1(i)
        k2(i)
        step(i)
        coord.pump()
    s = coord.stats()
    # hierarchical set: step-program and kernels side by side
    assert set(s["kernels"]) == {"matmul", "rmsnorm", "step_program"}
    # fairness under the shared budget: every unit got productive slots
    for name, k in s["kernels"].items():
        assert k["regenerations"] > 0, name
    # one budget bounds the SUM of all tuning time
    assert s["budget_spent_s"] <= s["budget_s"] + 1e-9
    # retire the step program only: kernels keep refreshing last_used
    spent_before = coord._aggregate_accounts().tuning_spent_s
    step_spent = step.tuner.accounts.tuning_spent_s
    assert step_spent > 0
    clock.advance(0.06)
    k1(0)
    k2(0)
    retired = coord.sweep()
    assert retired == [step] and step.state is TunerState.RETIRED
    # the tombstone keeps the shared budget honest
    agg = coord._aggregate_accounts()
    assert agg.tuning_spent_s == pytest.approx(spent_before)
    s = coord.stats()
    assert s["retired_accounts"]["tuning_spent_s"] == pytest.approx(
        step_spent)
    for f in ("gen_spent_s", "gen_stall_s", "eval_spent_s"):
        rollup = (sum(k[f] for k in s["kernels"].values())
                  + s["retired_accounts"][f])
        assert rollup == pytest.approx(s[f]), f


def test_kernel_handles_warm_start_from_registry():
    """A second process (same registry + generation cache + host clock)
    re-validates each kernel's persisted best with one regeneration and
    recompiles nothing."""
    from repro.core import GenerationCache, TunedRegistry

    registry = TunedRegistry()
    cache = GenerationCache()
    clock = VirtualClock()

    def run_process():
        coord = TuningCoordinator(
            policy=RegenerationPolicy(1.0, 0.5), device="test:v",
            clock=clock, registry=registry, async_generation=True,
            generation_cache=cache)
        plane = make_virtual_plane(clock, coord)
        h = plane.register_spec("rmsnorm", SPECS["rmsnorm"])
        # the budget gate paces regenerations at the candidate's full
        # predicted cost (gen + eval), so exhausting the space takes
        # ~space_size * gen_cost / per-call-cost iterations
        for i in range(6000):
            h(i)
            coord.pump()
            if h.tuner.explorer.finished:
                break
        for m in coord._managed:
            coord._flush_best(m)
        return h, coord.stats()

    h_cold, s_cold = run_process()
    assert h_cold.tuner.explorer.finished
    assert s_cold["gen_spent_s"] > 0
    h_warm, s_warm = run_process()
    assert h_warm.warm_started
    # the warm process re-proposes only cold-compiled points: pure hits
    assert s_warm["gen_spent_s"] == 0.0
    assert s_warm["gen_stall_s"] == 0.0
    assert (h_warm.tuner.explorer.best_point
            == h_cold.tuner.explorer.best_point)


def test_shared_plane_is_one_per_coordinator():
    """Serve builds its plane via shared(): request 2+ must reuse the
    handle memo and live-args table, not rebuild compilettes."""
    clock = VirtualClock()
    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:v", clock=clock)
    p1 = KernelTuningPlane.shared(
        coord, virtual=(clock, TPU_V5E), gen_cost_s=GEN_COST,
        evaluator_factory=lambda c: VirtualClockEvaluator(clock))
    p2 = KernelTuningPlane.shared(coord)
    assert p1 is p2
    h1 = p1.register_spec("rmsnorm", SPECS["rmsnorm"])
    h2 = p2.register_spec("rmsnorm", SPECS["rmsnorm"])
    assert h1 is h2
    # a different coordinator gets its own plane
    other = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:v", clock=clock)
    assert KernelTuningPlane.shared(other) is not p1


def test_shared_plane_reapplies_mutable_config():
    """A request that switches tuning mode must not inherit a stale
    adopt_points/strategies from the memoized plane."""
    clock = VirtualClock()
    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:v", clock=clock)
    p = KernelTuningPlane.shared(coord, adopt_points=True,
                                 strategies={"matmul": "greedy"})
    assert p.adopt_points and p.strategies == {"matmul": "greedy"}
    p2 = KernelTuningPlane.shared(coord, adopt_points=False,
                                  strategies={"rmsnorm": "random"})
    assert p2 is p
    assert p.adopt_points is False
    assert p.strategies == {"matmul": "greedy", "rmsnorm": "random"}


def test_converged_handle_releases_live_args():
    """Live call arguments are pinned only while the handle can still
    evaluate: convergence must drop the plane's reference too (the
    lifecycle already releases the evaluator closure)."""
    import jax.numpy as jnp2

    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:r")
    plane = KernelTuningPlane(coord, aot=False)
    x = jnp2.ones((16, 8), jnp2.float32)
    w = jnp2.ones((8,), jnp2.float32)
    for i in range(60):
        out = plane.call("rmsnorm", x, w)
        assert out is not None
        coord.pump()
        if all(m.tuner.explorer.finished for m in coord._managed):
            break
    coord.sweep()
    (m,) = coord._managed
    assert m.state is TunerState.CONVERGED
    assert m.tuner.evaluator.make_args is None
    # explicit prune releases the pinned live arguments…
    plane.prune_released()
    assert plane._live_args == {}
    # …and the fast-path memo still serves the converged best function
    # without re-pinning anything
    assert plane.call("rmsnorm", x, w) is not None
    assert plane._live_args == {}
    coord.close()


def test_parse_kernel_strategies_validates_both_sides():
    assert parse_kernel_strategies([]) is None
    assert parse_kernel_strategies(
        ["matmul=greedy", "attention=random"]) == {
            "matmul": "greedy", "attention": "random"}
    with pytest.raises(SystemExit):          # typo'd kernel: fail fast
        parse_kernel_strategies(["matmull=greedy"])
    with pytest.raises(SystemExit):          # unknown strategy
        parse_kernel_strategies(["matmul=simulated_annealing"])
    with pytest.raises(SystemExit):          # missing '='
        parse_kernel_strategies(["matmul"])


# ------------------------------------------------------ layers integration
def test_layers_route_rmsnorm_through_plane():
    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:r")
    plane = KernelTuningPlane(coord)
    from repro.models import layers

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    ref = layers.rms_norm(x, w)
    assert active_plane() is None
    with use_kernel_plane(plane):
        out = layers.rms_norm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    (m,) = coord._managed
    assert m.name == "rmsnorm"
    assert m.tuner.accounts.kernel_calls == 1
    # inside a jit trace the plane must NOT intercept (tracer args)…
    jitted = jax.jit(lambda x, w: layers.rms_norm(x, w))
    with use_kernel_plane(plane):
        out2 = jitted(x, w)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # …so no new handle appeared and no extra managed call was counted
    assert len(coord._managed) == 1
    assert m.tuner.accounts.kernel_calls == 1
    coord.close()


def test_traced_programs_adopt_tuned_attention_chunks():
    """Trace-time half of the plane: a jitted step-program picks up the
    attention kernel's best block sizes instead of cfg's hard-coded
    chunks — unless a program-level tuner owns those knobs."""
    from repro.configs import REGISTRY
    from repro.models.layers import plane_attn_chunks

    cfg = REGISTRY["deepseek-7b"].reduced()
    clock = VirtualClock()
    coord = TuningCoordinator(policy=RegenerationPolicy(1.0, 0.5),
                              device="test:v", clock=clock)
    plane = make_virtual_plane(clock, coord)
    h = plane.register_spec("attention", SPECS["attention"])
    for i in range(2000):
        h(i)
        coord.pump()
        if h.tuner.explorer.finished:
            break
    best = h.tuner.explorer.best_point
    assert best is not None
    assert plane_attn_chunks(cfg) == (cfg.attn_q_chunk, cfg.attn_k_chunk)
    with use_kernel_plane(plane):
        assert plane_attn_chunks(cfg) == (best["block_q"],
                                          best["block_kv"])
    # "both" mode: program points own the chunk knobs — no adoption
    plane.adopt_points = False
    with use_kernel_plane(plane):
        assert plane_attn_chunks(cfg) == (cfg.attn_q_chunk,
                                          cfg.attn_k_chunk)


# ------------------------------------------------- source-hash identity
def test_discovery_stamps_source_hash_of_ops_py():
    """Satellite: every discovered KERNEL carries the sha256 prefix of
    its defining ops.py, and the compilette turns it into a persistence
    fingerprint + cache-token suffix."""
    import hashlib
    import repro.kernels as pkg

    cat = get_catalog()
    for name in cat.names():
        defn = cat.get(name)
        src = None
        for root in pkg.__path__:
            p = pathlib.Path(root) / name / "ops.py"
            if p.is_file():
                src = p
                break
        assert src is not None, name
        expect = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
        assert defn.source_hash == expect, name
    comp = cat.compilette("rmsnorm", {"N": 16, "d": 8, "dtype": "float32"})
    h = cat.get("rmsnorm").source_hash
    assert comp.fingerprint_extra == f"src-{h}"
    assert comp.cache_token.endswith(f"src-{h}")


def test_edited_kernel_source_cold_starts_only_that_kernel():
    """Changing a kernel's source hash must miss its persisted best (the
    tuned point may be wrong for the new code) while an unchanged hash
    still warm-starts — and the registry fallback chain never crosses
    from one hash to another."""
    import dataclasses

    from repro.core import TunedRegistry

    registry = TunedRegistry()
    clock = VirtualClock()
    defn = get_catalog().get("rmsnorm")

    def run(source_hash):
        coord = TuningCoordinator(
            policy=RegenerationPolicy(1.0, 0.5), device="test:v",
            clock=clock, registry=registry, async_generation=True)
        comp = KernelCompilette(
            dataclasses.replace(defn, source_hash=source_hash),
            SPECS["rmsnorm"], virtual=(clock, TPU_V5E), gen_cost_s=GEN_COST)
        h = coord.register("rmsnorm", comp,
                           VirtualClockEvaluator(clock))
        for i in range(6000):
            h(i)
            coord.pump()
            if h.tuner.explorer.finished:
                break
        for m in coord._managed:
            coord._flush_best(m)
        return h

    cold = run("aaaa00000001")
    assert cold.tuner.explorer.finished
    assert cold.registry_device == "test:v:src-aaaa00000001"
    # same source: warm start hits the persisted best
    same = run("aaaa00000001")
    assert same.warm_started
    # edited source (different hash): cold start — stale best never leaks
    edited = run("bbbb00000002")
    assert not edited.warm_started
