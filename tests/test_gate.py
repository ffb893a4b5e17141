"""Trusted swaps: oracle gate, canaried promotion, quarantine persistence.

Everything deterministic runs on the ``VirtualClock`` + scripted gate
verdicts (virtual variants carry no numerics); the catalog-oracle checks
run the real kernels once on tiny shapes.
"""

import dataclasses

import pytest

from repro.core import (
    Compilette, FleetBus, OnlineAutotuner, Param, RegenerationPolicy,
    TunedRegistry, VariantGate, VirtualClock, VirtualClockEvaluator,
    product_space, virtual_kernel,
)
from repro.core.gate import GATE_MODES
from repro.runtime.coordinator import TuningCoordinator
from repro.runtime.lifecycle import TunerLifecycle


def make_virtual_compilette(clock, name, cost_fn):
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1,
                              switch_rank=0)])

    def gen(point, **spec):
        return virtual_kernel(clock, cost_fn(point), tag=dict(point))

    return Compilette(name, sp, gen)


def make_lying_compilette(clock, name, *, honest_s, lie_point,
                          lie_score_s, lie_serve_s):
    """Variants measure honestly except ``lie_point``, which reports
    ``lie_score_s`` to the evaluator but burns ``lie_serve_s`` per
    production call — the injected tail regression."""
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1,
                              switch_rank=0)])

    def gen(point, **spec):
        if dict(point) == lie_point:
            fn = virtual_kernel(clock, lie_serve_s, tag=dict(point))
            fn.score_s = lie_score_s
            return fn
        return virtual_kernel(clock, honest_s(point), tag=dict(point))

    return Compilette(name, sp, gen)


def run_tuner(tuner, calls=400):
    for i in range(calls):
        tuner(i)


# ----------------------------------------------------------------- gate
def test_gate_mode_validated():
    clock = VirtualClock()
    comp = make_virtual_compilette(clock, "k", lambda p: 0.01)
    with pytest.raises(ValueError):
        OnlineAutotuner(comp, VirtualClockEvaluator(clock),
                        gate_mode="sometimes")
    with pytest.raises(ValueError):
        TuningCoordinator(device="test:v", gate_mode="yes")
    assert GATE_MODES == ("off", "check", "canary")


def test_check_mode_blocks_wrong_variant_and_quarantines():
    """A scripted oracle failure on the best-measuring point: the point
    must never serve, be quarantined in the strategy (never re-proposed)
    and reported through the quarantine callback."""
    clock = VirtualClock()
    bad = {"unroll": 8}   # also the fastest — the dangerous case
    comp = make_virtual_compilette(
        clock, "k", lambda p: 0.010 / p["unroll"])
    comp.gate_script = lambda point: dict(point) != bad
    condemned = []
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="check",
        quarantine_cb=lambda p, reason: condemned.append((p, reason)))
    run_tuner(tuner)
    s = tuner.stats()
    assert s["gate_checks"] >= 3
    assert s["gate_failures"] == 1
    assert s["quarantined"] == 1
    assert condemned and condemned[0][0] == bad
    assert "oracle" in condemned[0][1]
    assert tuner.explorer.is_quarantined(bad)
    # the gate caught it before it could serve: active is the best of
    # the variants that PASSED, and the bad point never served a call
    assert s["active_point"] == {"unroll": 4}
    assert s["swaps"] >= 1
    assert all(life.point != bad or life.calls == 0
               for life in tuner._lives)


def test_check_mode_passes_clean_variants_unchanged():
    clock = VirtualClock()
    comp = make_virtual_compilette(
        clock, "k", lambda p: 0.010 / p["unroll"])
    # virtual marker: the gate bills its natural cost (one simulated
    # execution of the variant) to the virtual clock
    comp.virtual = (clock, None)
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="check")
    run_tuner(tuner)
    s = tuner.stats()
    assert s["gate_failures"] == 0
    assert s["quarantined"] == 0
    assert s["active_point"] == {"unroll": 8}
    # the checks billed their cost: one simulated execution each
    assert s["gate_spent_s"] > 0.0
    assert s["tuning_spent_s"] >= s["gate_spent_s"]


# --------------------------------------------------------------- canary
def test_canary_promotes_clean_variant_after_probation():
    clock = VirtualClock()
    comp = make_virtual_compilette(
        clock, "k", lambda p: 0.010 / p["unroll"])
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="canary",
        canary_fraction=0.5, canary_calls=4)
    run_tuner(tuner)
    s = tuner.stats()
    assert s["canary_promotions"] >= 1
    assert s["swaps"] == s["canary_promotions"]   # canary mode: no direct swaps
    assert s["rollbacks"] == 0
    assert s["canary_calls"] >= 4
    assert s["active_point"] == {"unroll": 8}
    assert not s["canary_in_flight"]


def test_canary_tail_regression_rolls_back_and_quarantines():
    """The variant measures 2x faster than the incumbent but serves 4x
    slower: the canary's observed mean latency trips the regression
    limit, the incumbent takes back every call, the point is condemned."""
    clock = VirtualClock()
    lie = {"unroll": 8}
    comp = make_lying_compilette(
        clock, "k", honest_s=lambda p: 0.010, lie_point=lie,
        lie_score_s=0.005, lie_serve_s=0.040)
    condemned = []
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="canary",
        canary_fraction=0.5, canary_calls=4,
        quarantine_cb=lambda p, reason: condemned.append((p, reason)))
    run_tuner(tuner)
    s = tuner.stats()
    assert s["rollbacks"] == 1
    assert s["quarantined"] == 1
    assert s["canary_promotions"] == 0
    assert s["swaps"] == 0
    assert tuner.explorer.is_quarantined(lie)
    assert condemned and condemned[0][0] == lie
    assert "tail regression" in condemned[0][1]
    # the incumbent (reference) still serves
    assert s["active_point"] is None
    assert tuner.last_served_point is None


def test_canary_raising_variant_rolls_back_and_caller_never_sees_it():
    clock = VirtualClock()
    bad = {"unroll": 8}
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1,
                              switch_rank=0)])

    def gen(point, **spec):
        if dict(point) == bad:
            fn = virtual_kernel(clock, 0.004, tag=dict(point))

            def raising(*args):
                raise RuntimeError("bad codegen")
            raising.score_s = fn.score_s
            raising.tag = fn.tag
            return raising
        return virtual_kernel(clock, 0.010, tag=dict(point))

    comp = Compilette("k", sp, gen)
    # the gate's virtual path would catch the raise at check time; give
    # this compilette no virtual marker so the raise surfaces in canary
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="canary",
        canary_fraction=0.5, canary_calls=4)
    outs = [tuner(i) for i in range(400)]
    s = tuner.stats()
    assert s["rollbacks"] == 1
    assert tuner.explorer.is_quarantined(bad)
    # every production call got a real answer (incumbent covered the raise)
    assert all(out is not None for out in outs)


def test_better_candidate_supersedes_canary_without_quarantine():
    """A newer, faster candidate replaces an unfinished canary: the old
    canary lost the race but did nothing wrong — no quarantine."""
    clock = VirtualClock()
    comp = make_virtual_compilette(
        clock, "k", lambda p: 0.010 / p["unroll"])
    tuner = OnlineAutotuner(
        comp, VirtualClockEvaluator(clock),
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        clock=clock, wake_every=1, gate=VariantGate(comp), gate_mode="canary",
        canary_fraction=0.25, canary_calls=1000)   # probation never ends
    run_tuner(tuner)
    s = tuner.stats()
    assert s["quarantined"] == 0
    assert s["rollbacks"] == 0
    assert s["canary_promotions"] == 0
    assert s["canary_in_flight"]          # the last best still on probation
    assert s["active_point"] is None      # reference never displaced
    assert tuner._canary.life.point == {"unroll": 8}


# ---------------------------------------------------- quarantine persistence
def test_registry_quarantine_survives_save_load(tmp_path):
    reg = TunedRegistry()
    spec, dev, point = {"N": 64}, "test:v", {"unroll": 8}
    reg.put("k", spec, dev, point, 0.001)
    assert reg.get("k", spec, dev) == point
    reg.quarantine("k", spec, dev, point, "oracle mismatch")
    # quarantine drops the matching best immediately
    assert reg.get("k", spec, dev) is None
    assert reg.is_quarantined("k", spec, dev, point)

    path = str(tmp_path / "tuned.json")
    reg.save(path)
    back = TunedRegistry.load(path)
    assert back.is_quarantined("k", spec, dev, point)
    assert back.n_quarantined == 1
    assert back.get_warm("k", spec, dev) is None
    assert back.quarantined_points("k", spec, dev) == [point]


def test_coordinator_never_re_trusts_quarantined_point_after_restart():
    """Warm-start path: a condemned point must neither seed the tuner nor
    ever be proposed again by its strategy."""
    clock = VirtualClock()
    reg = TunedRegistry()
    coord = TuningCoordinator(device="test:v", clock=clock, registry=reg,
                              gate_mode="check")
    comp = make_virtual_compilette(clock, "k", lambda p: 0.010)
    bad = {"unroll": 8}
    # a previous process found `bad` best, then condemned it
    reg.put("k", {}, coord.device, bad, 0.001)
    reg.quarantine("k", {}, coord.device, bad, "tail regression")
    m = coord.register("k", comp, VirtualClockEvaluator(clock),
                       reference_fn=virtual_kernel(clock, 0.010))
    assert not m.warm_started
    assert m.tuner.explorer.is_quarantined(bad)
    m.tuner.exhaust()
    assert m.tuner.explorer.best_point != bad
    assert bad not in [life.point for life in m.tuner._lives]


def test_autotuner_quarantine_writes_through_to_registry():
    clock = VirtualClock()
    reg = TunedRegistry()
    coord = TuningCoordinator(
        device="test:v", clock=clock, registry=reg, gate_mode="check",
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0))
    comp = make_virtual_compilette(clock, "k", lambda p: 0.010 / p["unroll"])
    bad = {"unroll": 8}
    comp.gate_script = lambda point: dict(point) != bad
    m = coord.register("k", comp, VirtualClockEvaluator(clock),
                       reference_fn=virtual_kernel(clock, 0.010))
    while not m.tuner.explorer.finished:
        m(1)
        coord.pump()
    assert reg.is_quarantined("k", {}, m.registry_device, bad)
    # and a later process seeded from this registry skips it outright
    coord2 = TuningCoordinator(device="test:v", clock=clock, registry=reg,
                               gate_mode="check")
    comp2 = make_virtual_compilette(clock, "k", lambda p: 0.010)
    m2 = coord2.register("k", comp2, VirtualClockEvaluator(clock),
                         reference_fn=virtual_kernel(clock, 0.010))
    assert m2.tuner.explorer.is_quarantined(bad)


# ------------------------------------------------------------ stats rollup
def test_coordinator_stats_reconcile_gate_and_canary_counters():
    """Top-level aggregates == sum(per-kernel) + retired tombstone for
    every trusted-swaps counter, including after a tuner retires."""
    clock = VirtualClock()
    coord = TuningCoordinator(
        device="test:v", clock=clock, gate_mode="canary",
        canary_fraction=0.5, canary_calls=2,
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        lifecycle=TunerLifecycle(idle_evict_s=50.0))
    ev = VirtualClockEvaluator(clock)
    bad = {"unroll": 4}
    comp_a = make_virtual_compilette(clock, "a", lambda p: 0.010 / p["unroll"])
    comp_a.gate_script = lambda point: dict(point) != bad
    comp_b = make_virtual_compilette(clock, "b", lambda p: 0.020 / p["unroll"])
    a = coord.register("a", comp_a, ev,
                       reference_fn=virtual_kernel(clock, 0.010))
    b = coord.register("b", comp_b, ev,
                       reference_fn=virtual_kernel(clock, 0.020))
    for i in range(300):
        a(i)
        b(i)
        coord.pump()
    fields = ("gate_spent_s", "gate_checks", "gate_failures",
              "canary_calls", "canary_promotions", "rollbacks",
              "quarantined", "swaps")

    def assert_reconciles():
        s = coord.stats()
        for f in fields:
            parts = (sum(k[f] for k in s["kernels"].values())
                     + s["retired_accounts"][f])
            assert parts == pytest.approx(s[f]), f
        return s

    s = assert_reconciles()
    assert s["gate_mode"] == "canary"
    assert s["gate_checks"] >= 6
    assert s["gate_failures"] >= 1
    assert s["quarantined"] >= 1
    assert s["canary_promotions"] >= 1

    # retire kernel "a" (idle past the eviction horizon): its counters
    # move to the tombstone and the aggregates must not change
    before = {f: coord.stats()[f] for f in fields}
    for i in range(300):
        b(i)
        clock.advance(1.0)
        coord.pump()
    s = assert_reconciles()
    assert s["lifecycle"]["retired"] >= 1
    for f in ("gate_checks", "gate_failures", "quarantined"):
        assert s[f] >= before[f]
    assert s["retired_accounts"]["gate_checks"] >= 1


# --------------------------------------------------------- catalog oracles
def test_every_catalog_kernel_declares_an_oracle():
    from repro.kernels.catalog import get_catalog

    catalog = get_catalog()
    assert len(catalog.names()) >= 5
    for name in catalog.names():
        defn = catalog.get(name)
        assert defn.oracle is not None, f"{name} has no ref.py oracle"
        tol = dict(defn.tolerance or {})
        assert 0 < tol.get("rtol", 0) <= 1e-2, f"{name} tolerance {tol}"


def test_decode_attention_matches_its_oracle():
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    B, S, H, Hk, Dh = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hk, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hk, Dh), jnp.float32)
    length = jnp.array([40, 64])
    got = decode_attention(q, k, v, length=length, k_chunk=16)
    want = decode_attention_ref(q, k, v, length)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3


def test_example_args_are_never_constant():
    """Constant example fills make the oracle gate vacuous — euclid's
    distance between identical all-ones rows is exactly 0, so any
    multiplicative corruption compares equal to the reference. Every
    kernel must feed the gate varied data."""
    import numpy as np

    from repro.kernels.catalog import get_catalog

    specs = {
        "matmul": {"M": 64, "N": 128, "K": 128, "dtype": "float32"},
        "attention": {"B": 1, "Tq": 16, "Tkv": 16, "H": 2, "Hk": 1,
                      "Dh": 8, "causal": True, "dtype": "float32"},
        "decode_attention": {"B": 2, "S": 64, "H": 4, "Hk": 2, "Dh": 16,
                             "dtype": "float32"},
        "rmsnorm": {"N": 16, "d": 8, "dtype": "float32"},
        "lintra": {"H": 8, "W": 16, "bands": 3, "dtype": "float32"},
        "euclid": {"N": 128, "M": 64, "D": 32, "dtype": "float32"},
    }
    cat = get_catalog()
    assert set(specs) == set(cat.names())
    for name, spec in specs.items():
        for arr in cat.get(name).example_args(spec):
            a = np.asarray(arr)
            if a.ndim == 0:
                continue             # scalars (decode_attention length)
            assert a.std() > 0, f"{name}: constant example array"


def test_gate_rejects_corrupted_variant_on_real_numerics():
    """End to end on the real XLA backend: a genuinely generated euclid
    variant passes the oracle gate, the same variant scaled by 1.5x is
    rejected with the kernel's own tolerance in the reason."""
    from repro.kernels.catalog import get_catalog

    comp = get_catalog().compilette(
        "euclid", {"N": 128, "M": 64, "D": 32, "dtype": "float32"})
    point = next(iter(comp.space.iter_valid()))
    kern = comp.generate(point)
    gate = VariantGate(comp)
    ok, reason = gate.check(point, kern.fn)
    assert ok, reason
    ok, reason = gate.check(point, lambda *a: kern.fn(*a) * 1.5)
    assert not ok and "err" in reason
    assert gate.checks == 2 and gate.failures == 1


@pytest.mark.parametrize("name,spec", [
    ("attention", {"B": 2, "Tq": 128, "Tkv": 128, "H": 4, "Hk": 4,
                   "Dh": 128, "causal": True, "dtype": "bfloat16"}),
    ("decode_attention", {"B": 2, "S": 128, "H": 4, "Hk": 4, "Dh": 128,
                          "dtype": "bfloat16"}),
    ("rmsnorm", {"N": 64, "d": 256, "dtype": "bfloat16"}),
])
def test_gate_judges_bf16_variants_at_bf16_resolution(name, spec):
    """A bf16 variant and its float32-computed oracle land a few bf16
    roundings apart (flash attention feeds bf16 probabilities to its
    second matmul): the gate passes the correct variant, and still
    rejects one whose output is 10% off."""
    import jax.numpy as jnp

    from repro.kernels.catalog import get_catalog

    comp = get_catalog().compilette(name, spec)
    point = next(iter(comp.space.iter_valid()))
    kern = comp.generate(point)
    gate = VariantGate(comp)
    ok, reason = gate.check(point, kern.fn)
    assert ok, reason
    ok, reason = gate.check(point, lambda *a: (
        kern.fn(*a).astype(jnp.float32) * 1.1).astype(jnp.bfloat16))
    assert not ok and "err" in reason


def test_variant_gate_uses_catalog_oracle_and_tolerance():
    """Real-numerics path: the gate passes the kernel's own reference and
    fails a deliberately wrong function, using KernelDef tolerances."""
    from repro.kernels.catalog import get_catalog

    defn = get_catalog().get("euclid")
    spec = {"N": 16, "M": 8, "D": 8, "dtype": "float32"}
    comp = get_catalog().compilette("euclid", spec)
    gate = VariantGate(comp)
    assert gate.rtol == dict(defn.tolerance)["rtol"]
    ok, _ = gate.check({"p": 1}, defn.oracle)
    assert ok
    ok, reason = gate.check({"p": 2}, lambda x, c: defn.oracle(x, c) + 1.0)
    assert not ok and "err" in reason
    assert gate.checks == 2 and gate.failures == 1


# ------------------------------------------------------------ compile farm
def test_compile_farm_workers_survive_failures():
    """A raising generate and a raising charge callback each produce a
    failed ticket (billed, quarantinable) — never a dead worker slot."""
    from repro.core.compile_farm import CompileFarm

    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1)])
    calls = {"n": 0}

    def gen(point, **spec):
        calls["n"] += 1
        if point["unroll"] == 2:
            raise RuntimeError("codegen exploded")
        return lambda x: x

    comp = Compilette("k", sp, gen)
    farm = CompileFarm(mode="thread", workers=2)
    try:
        t_bad = farm.submit(comp, {"unroll": 2}, {})
        t_good = farm.submit(comp, {"unroll": 4}, {})
        charges = []

        def bad_cb(ticket, seconds):
            charges.append(seconds)
            raise RuntimeError("account gone")

        t_spec = farm.submit(comp, {"unroll": 8}, {},
                             speculative=True, charge_cb=bad_cb)

        def wait(*tickets):
            import threading
            for _ in range(2000):
                if all(t.done for t in tickets):
                    return
                threading.Event().wait(0.005)
            raise AssertionError("farm tickets never completed")

        wait(t_bad, t_good, t_spec)
        assert t_bad.error is not None and t_bad.kern is None
        assert t_good.error is None and t_good.kern is not None
        assert t_spec.done
        assert charges                       # the farm did try to bill
        assert farm.worker_errors >= 1       # ...and logged the escape
        # the pool is intact: a fresh job still completes
        t_again = farm.submit(comp, {"unroll": 1}, {})
        wait(t_again)
        assert t_again.error is None
        s = farm.stats()
        assert s["completed"] >= 3 and s["failed"] >= 1
    finally:
        farm.shutdown()


# ------------------------------------------------------------ config knobs
def test_tuning_config_gate_knobs_env_flags_alias():
    import argparse

    from repro.api import TuningConfig

    cfg = TuningConfig.from_env({
        "REPRO_TUNE_GATE": "canary",              # alias -> gate_mode
        "REPRO_TUNE_CANARY_FRACTION": "0.5",
        "REPRO_TUNE_CANARY_CALLS": "16",
        "REPRO_TUNE_GATE_RTOL": "1e-2",
    })
    assert cfg.gate_mode == "canary"
    assert cfg.canary_fraction == 0.5
    assert cfg.canary_calls == 16
    assert cfg.gate_rtol == 1e-2
    assert cfg.gate_atol is None

    ap = argparse.ArgumentParser()
    TuningConfig.add_flags(ap)
    args = ap.parse_args(["--gate-mode", "check", "--canary-calls", "3",
                          "--gate-atol", "1e-6"])
    cfg = TuningConfig.from_flags(args)
    assert cfg.gate_mode == "check"
    assert cfg.canary_calls == 3
    assert cfg.gate_atol == 1e-6

    with pytest.raises(ValueError):
        TuningConfig(gate_mode="nope")
    with pytest.raises(ValueError):
        TuningConfig(canary_fraction=0.0)
    with pytest.raises(ValueError):
        TuningConfig(canary_calls=0)


# ------------------------------------------------------ fault-injection replay
def test_fault_replay_wrong_output_serves_zero_calls():
    from repro.api import TuningConfig
    from repro.bench.replay import (
        fault_scenarios, replay_scenario, replay_tuning_defaults)
    from repro.configs import REGISTRY

    gated = dataclasses.replace(replay_tuning_defaults(),
                                gate_mode="canary")
    configs = {"deepseek-7b": REGISTRY["deepseek-7b"]}
    by_name = {sc.name: sc for sc in fault_scenarios(320)}

    r = replay_scenario(by_name["wrong_output_variant"], configs,
                        seed=0, config=gated)
    t = r["tuning"]
    assert t["gate_mode"] == "canary"
    assert t["gate_failures"] >= 1
    assert t["quarantined"] >= t["gate_failures"]
    assert t["served_wrong_calls"] == 0
    assert t["overhead_pct"] <= 5.0


def test_fault_replay_tail_regression_rolls_back():
    from repro.bench.replay import (
        fault_scenarios, replay_scenario, replay_tuning_defaults)
    from repro.configs import REGISTRY

    gated = dataclasses.replace(replay_tuning_defaults(),
                                gate_mode="canary")
    configs = {"deepseek-7b": REGISTRY["deepseek-7b"]}
    by_name = {sc.name: sc for sc in fault_scenarios(320)}

    r = replay_scenario(by_name["tail_regression"], configs,
                        seed=0, config=gated)
    t = r["tuning"]
    assert t["rollbacks"] >= 1
    assert t["quarantined"] >= t["rollbacks"]
    assert t["overhead_pct"] <= 5.0
    # the rollback restored service: still at least as fast as reference
    assert all(pt["speedup_vs_ref"] >= 1.0
               for pt in r["per_tenant"].values())


def test_fault_replay_compile_failures_quarantine_without_stall():
    from repro.bench.replay import (
        fault_scenarios, replay_scenario, replay_tuning_defaults)
    from repro.configs import REGISTRY

    gated = dataclasses.replace(replay_tuning_defaults(),
                                gate_mode="canary")
    configs = {"deepseek-7b": REGISTRY["deepseek-7b"]}
    by_name = {sc.name: sc for sc in fault_scenarios(320)}

    r = replay_scenario(by_name["faulty_compiles_burst"], configs,
                        seed=0, config=gated)
    t = r["tuning"]
    assert t["quarantined"] >= 1
    assert t["served_wrong_calls"] == 0
    assert t["overhead_pct"] <= 5.0


# ------------------------------------------------------------ fleet gate
def _fleet_canary_coordinator(clock, *, rid, bus):
    return TuningCoordinator(
        device="test:v", clock=clock, registry=TunedRegistry(),
        gate_mode="canary", canary_fraction=0.5, canary_calls=4,
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=1.0),
        replica_id=rid, replica_count=2, registry_backend=bus,
        sync_every_s=None)


def test_canary_rollback_quarantines_fleet_wide():
    """A tail regression caught by replica 0's canary condemns the point
    for the whole fleet: after one sync, replica 1 holds the quarantine,
    never serves the lying point, and adopts replica 0's honest best as a
    CANDIDATE through its own canary — one rollback per fleet, not one
    per replica."""
    bus = FleetBus()
    lie = {"unroll": 8}
    clock_a, clock_b = VirtualClock(), VirtualClock()
    coord_a = _fleet_canary_coordinator(clock_a, rid=0, bus=bus)
    coord_b = _fleet_canary_coordinator(clock_b, rid=1, bus=bus)

    def lying(clock):
        return make_lying_compilette(
            clock, "k", honest_s=lambda p: 0.010 / p["unroll"],
            lie_point=lie, lie_score_s=0.001, lie_serve_s=0.040)

    m_a = coord_a.register("k", lying(clock_a), VirtualClockEvaluator(clock_a),
                           reference_fn=virtual_kernel(clock_a, 0.010))
    m_b = coord_b.register("k", lying(clock_b), VirtualClockEvaluator(clock_b),
                           reference_fn=virtual_kernel(clock_b, 0.010))
    # all unroll points stripe to replica 0: replica 1 owns nothing and
    # can only ever receive work through the fleet adoption path
    for i in range(50):
        m_b(i)
        clock_b.advance(0.010)
        coord_b.observe_busy(0.010)
        coord_b.pump()
    assert m_b.tuner.explorer.finished

    for i in range(400):
        m_a(i)
        clock_a.advance(0.010)
        coord_a.observe_busy(0.010)
        coord_a.pump()
    s_a = m_a.tuner.stats()
    assert s_a["rollbacks"] == 1
    assert m_a.tuner.explorer.is_quarantined(lie)
    coord_a.sync_fleet()

    coord_b.sync_fleet()
    assert m_b.tuner.explorer.is_quarantined(lie)
    for i in range(400):
        m_b(i)
        clock_b.advance(0.010)
        coord_b.observe_busy(0.010)
        coord_b.pump()
    s_b = m_b.tuner.stats()
    # the fleet paid for exactly one rollback; the peer adopted the
    # verdict instead of re-learning it in production
    assert s_b["rollbacks"] == 0
    assert s_b["gate_failures"] == 0
    assert all(life.point != lie or life.calls == 0
               for life in m_b.tuner._lives)
    # peer best arrived as a canaried CANDIDATE, never a blind incumbent
    assert s_b["canary_promotions"] >= 1
    assert s_b["swaps"] == s_b["canary_promotions"]
    assert s_b["active_point"] == {"unroll": 4}


def test_fleet_quarantine_blocks_warm_start_after_restart():
    """Replica 1 restarts from the merged fleet state: the condemned
    point neither warm-starts nor re-enters its strategy even though the
    registry file never saw replica 1 condemn anything itself."""
    bus = FleetBus()
    lie = {"unroll": 8}
    clock_a = VirtualClock()
    coord_a = _fleet_canary_coordinator(clock_a, rid=0, bus=bus)
    comp_a = make_lying_compilette(
        clock_a, "k", honest_s=lambda p: 0.010 / p["unroll"],
        lie_point=lie, lie_score_s=0.001, lie_serve_s=0.040)
    m_a = coord_a.register("k", comp_a, VirtualClockEvaluator(clock_a),
                           reference_fn=virtual_kernel(clock_a, 0.010))
    for i in range(400):
        m_a(i)
        clock_a.advance(0.010)
        coord_a.observe_busy(0.010)
        coord_a.pump()
    coord_a.sync_fleet()

    # a fresh replica-1 process joining the fleet after the fact
    clock_b = VirtualClock()
    coord_b = _fleet_canary_coordinator(clock_b, rid=1, bus=bus)
    comp_b = make_virtual_compilette(clock_b, "k",
                                     lambda p: 0.010 / p["unroll"])
    m_b = coord_b.register("k", comp_b, VirtualClockEvaluator(clock_b),
                           reference_fn=virtual_kernel(clock_b, 0.010))
    coord_b.sync_fleet()
    assert m_b.tuner.explorer.is_quarantined(lie)
    assert not m_b.warm_started or m_b.tuner.stats()["active_point"] != lie
    for i in range(200):
        m_b(i)
        clock_b.advance(0.010)
        coord_b.observe_busy(0.010)
        coord_b.pump()
    assert all(life.point != lie or life.calls == 0
               for life in m_b.tuner._lives)
    assert m_b.tuner.stats()["rollbacks"] == 0


# ---------------------------------------------------------- transfer gate
def test_transfer_seed_faulted_oracle_quarantines_fleet_wide():
    """Transfer fault row: a trait-similar device receives a foreign best
    as a transfer seed, its (fault-injected) oracle rejects it — the
    point must quarantine fleet-wide and never be re-seeded on ANY
    similar device, which must still converge to an honest best."""
    from repro.bench.replay import fault_injection_hook
    from repro.core.profiles import TI_L3, scaled_profile

    def comp_on(clock, profile):
        comp = make_virtual_compilette(clock, "k",
                                       lambda p: 0.010 / p["unroll"])
        comp.virtual = (clock, profile)
        return comp

    def coordinator(clock, device):
        return TuningCoordinator(
            device=device, clock=clock, registry=reg, transfer=True,
            gate_mode="check",
            policy=RegenerationPolicy(max_overhead_frac=1.0,
                                      invest_frac=1.0))

    def drive(coord, m, clock, n=300):
        for i in range(n):
            m(i)
            clock.advance(0.010)
            coord.observe_busy(0.010)
            coord.pump()

    reg = TunedRegistry()
    # donor: clean device publishes its best (with traits)
    clock_a = VirtualClock()
    coord_a = coordinator(clock_a, "bench:donor")
    m_a = coord_a.register("k", comp_on(clock_a, TI_L3),
                           VirtualClockEvaluator(clock_a),
                           reference_fn=virtual_kernel(clock_a, 0.010))
    drive(coord_a, m_a, clock_a)
    best = {"unroll": 8}
    assert m_a.tuner.explorer.best_point == best

    # device B (similar profile): EVERY non-base variant is miscompiled —
    # the transferred best must fail B's oracle, not serve, and condemn
    clock_b = VirtualClock()
    coord_b = coordinator(clock_b, "bench:b")
    comp_b = comp_on(clock_b, scaled_profile(TI_L3, "TI-L3~", flops=1.2))
    fault_injection_hook({"wrong_output_rate": 1.0}, seed=0,
                         clock=clock_b)(comp_b)
    m_b = coord_b.register("k", comp_b, VirtualClockEvaluator(clock_b),
                           reference_fn=virtual_kernel(clock_b, 0.010))
    assert m_b.transfer_seed_keys, "similar device must receive the seed"
    drive(coord_b, m_b, clock_b)
    s_b = m_b.tuner.stats()
    assert s_b["gate_failures"] >= 1
    assert m_b.tuner.explorer.is_quarantined(best)
    assert reg.is_quarantined("k", {}, "bench:b", best)
    assert all(life.point != best or life.calls == 0
               for life in m_b.tuner._lives), (
        "a faulted transfer seed must never serve a production call")
    assert coord_b.stats()["transfer_adopted"] == 0

    # device C (similar to both): the condemned point never travels again
    clock_c = VirtualClock()
    coord_c = coordinator(clock_c, "bench:c")
    comp_c = comp_on(clock_c, scaled_profile(TI_L3, "TI-L3≈",
                                             bandwidth=1.1))
    m_c = coord_c.register("k", comp_c, VirtualClockEvaluator(clock_c),
                           reference_fn=virtual_kernel(clock_c, 0.010))
    bad_key = comp_c.space.key(best)
    assert bad_key not in m_c.transfer_seed_keys, (
        "a seed condemned anywhere in the fleet must not be re-seeded "
        "on any similar device")
    drive(coord_c, m_c, clock_c)
    # C still converges honestly (its own gate is clean)
    assert m_c.tuner.explorer.best_point == best
    assert m_c.tuner.stats()["gate_failures"] == 0
