"""Tuner lifecycle: bucketing, convergence, eviction, registry hygiene.

Control-loop tests run on the ``VirtualClock``; the serve-loop tests run
the real (reduced) model end-to-end to show bucketing/eviction on the
actual request path.
"""

import dataclasses
import os
import tempfile

import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.core import (
    Compilette,
    Param,
    RegenerationPolicy,
    TunedRegistry,
    VirtualClock,
    VirtualClockEvaluator,
    compiler_version,
    device_fallbacks,
    device_fingerprint,
    product_space,
    virtual_kernel,
)
from repro.runtime.coordinator import TuningCoordinator
from repro.runtime.lifecycle import (
    TunerLifecycle,
    TunerState,
    pow2_bucket,
    release_evaluator_closure,
)


def make_virtual_compilette(clock, name="k"):
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1)])

    def gen(point, **spec):
        return virtual_kernel(clock, 0.008 / point["unroll"])

    return Compilette(name, sp, gen)


# --------------------------------------------------------------- bucketing
def test_pow2_bucket_rounds_in_log_space():
    assert pow2_bucket(1) == 1
    assert pow2_bucket(2) == 2
    assert pow2_bucket(120) == 128
    assert pow2_bucket(150) == 128    # geometric midpoint of 128/256 ≈ 181
    assert pow2_bucket(200) == 256
    assert pow2_bucket(128) == 128
    # boundary: n^2 == lo*hi goes to the smaller bucket
    assert pow2_bucket(181) == 128
    assert pow2_bucket(182) == 256


def test_bucket_specialization_only_touches_shape_keys():
    lc = TunerLifecycle(seq_buckets=True)
    spec = {"seq": 150, "max_len": 200, "batch": 3, "dtype": "bf16"}
    out = lc.bucket_specialization(spec)
    assert out == {"seq": 128, "max_len": 256, "batch": 3, "dtype": "bf16"}
    assert spec["seq"] == 150          # input not mutated
    off = TunerLifecycle(seq_buckets=False)
    assert off.bucket_specialization(spec) == spec
    assert off.bucket_length(150) == 150


def test_coordinator_buckets_share_one_tuner():
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock,
        lifecycle=TunerLifecycle(seq_buckets=True, idle_evict_s=None))
    comp = make_virtual_compilette(clock)
    a = coord.register("prefill", comp, ev, specialization={"seq": 120},
                       reference_fn=virtual_kernel(clock, 0.008))
    b = coord.register("prefill", comp, ev, specialization={"seq": 150},
                       reference_fn=virtual_kernel(clock, 0.008))
    assert a is b
    assert a.specialization == {"seq": 128}
    assert coord.stats()["n_kernels"] == 1
    # a genuinely different bucket gets its own tuner
    c = coord.register("prefill", comp, ev, specialization={"seq": 300},
                       reference_fn=virtual_kernel(clock, 0.008))
    assert c is not a and c.specialization == {"seq": 256}


# ------------------------------------------------------------- convergence
def drive_to_convergence(coord, m, calls=500):
    for i in range(calls):
        m(i)
        coord.pump()
        if m.tuner.explorer.finished:
            break
    coord.sweep()


def test_converged_tuner_releases_closure_but_keeps_serving():
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    ev.make_args = lambda: ()          # simulate a pinned request closure
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock)
    m = coord.register("k", make_virtual_compilette(clock), ev,
                       reference_fn=virtual_kernel(clock, 0.008))
    drive_to_convergence(coord, m)
    assert m.state is TunerState.CONVERGED
    assert ev.make_args is None                    # closure released
    assert coord.stats()["lifecycle"]["converged"] == 1
    # still registered and still serving its tuned best function
    again = coord.register("k", make_virtual_compilette(clock), ev,
                           reference_fn=virtual_kernel(clock, 0.008))
    assert again is m
    assert m.tuner._active_life.point == {"unroll": 8}
    # a re-pinned closure (serve re-registers per request) is re-released
    ev.make_args = lambda: ()
    coord.sweep()
    assert ev.make_args is None


# ---------------------------------------------------------------- eviction
def test_idle_tuner_is_evicted_with_closure_released():
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    ev.make_args = lambda: ()
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock,
        lifecycle=TunerLifecycle(seq_buckets=True, idle_evict_s=10.0))
    m = coord.register("k", make_virtual_compilette(clock), ev,
                       reference_fn=virtual_kernel(clock, 0.008))
    for i in range(50):
        m(i)
        coord.pump()
    spent_before = coord._aggregate_accounts().tuning_spent_s
    assert spent_before > 0
    clock.advance(11.0)                # idle past the eviction horizon
    retired = coord.sweep()
    assert retired == [m]
    assert m.state is TunerState.RETIRED
    assert ev.make_args is None                    # closure released
    assert coord.stats()["n_kernels"] == 0
    assert coord.stats()["lifecycle"]["retired"] == 1
    # the shared budget keeps counting what the retired tuner spent
    assert coord._aggregate_accounts().tuning_spent_s == \
        pytest.approx(spent_before)
    # its best point was flushed: a re-register warm-starts
    again = coord.register("k", make_virtual_compilette(clock), ev,
                           reference_fn=virtual_kernel(clock, 0.008))
    assert again is not m
    assert again.warm_started


def test_busy_tuner_is_not_evicted():
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock,
        lifecycle=TunerLifecycle(seq_buckets=True, idle_evict_s=10.0))
    m = coord.register("k", make_virtual_compilette(clock), ev,
                       reference_fn=virtual_kernel(clock, 0.008))
    for _ in range(2000):
        m(1)                           # keeps touching last_used_s
        coord.pump()
        assert coord.sweep() == []
    assert m.state in (TunerState.ACTIVE, TunerState.CONVERGED)


def test_release_evaluator_closure_tolerates_any_evaluator():
    clock = VirtualClock()
    release_evaluator_closure(object())                   # no evaluator attr
    tuner = type("T", (), {"evaluator": VirtualClockEvaluator(clock)})()
    release_evaluator_closure(tuner)                      # no make_args attr


# ----------------------------------------------- compiler-version keys
def test_device_fingerprint_includes_compiler_version():
    fp = device_fingerprint()
    assert compiler_version() in fp
    assert fp.count(":") >= 2


def test_stale_compiler_entry_degrades_to_cold_start():
    """An entry persisted under an older jax/jaxlib has a different
    fingerprint: exact lookup misses, and the fallback chain must NOT
    resurrect it (only versionless legacy layouts fall back)."""
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    reg = TunedRegistry()
    reg.put("k", {}, "cpu:x:jax0.1-jaxlib0.1", {"unroll": 8}, 0.001)
    coord = TuningCoordinator(
        registry=reg, device=f"cpu:x:{compiler_version()}", clock=clock)
    m = coord.register("k", make_virtual_compilette(clock), ev,
                       reference_fn=virtual_kernel(clock, 0.008))
    assert not m.warm_started


def test_legacy_layout_entries_still_warm_start():
    clock = VirtualClock()
    ev = VirtualClockEvaluator(clock)
    device = f"cpu:x:{compiler_version()}"
    assert device_fallbacks(device) == ("cpu:x", "x")
    for legacy_key in ("cpu:x", "x"):
        reg = TunedRegistry()
        reg.put("k", {}, legacy_key, {"unroll": 8}, 0.001)
        coord = TuningCoordinator(registry=reg, device=device, clock=clock)
        m = coord.register(
            f"k", make_virtual_compilette(clock), ev,
            reference_fn=virtual_kernel(clock, 0.008))
        assert m.warm_started, legacy_key


# ---------------------------------------------------------- registry aging
def test_registry_entry_ages_out_after_idle_saves():
    """An entry untouched for max_idle_saves save cycles is compacted."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        reg = TunedRegistry(max_idle_saves=3)
        reg.put("dead", {}, "test:v", {"unroll": 1}, 0.1)
        reg.put("live", {}, "test:v", {"unroll": 8}, 0.1)
        for _ in range(3):
            reg.get("live", {}, "test:v")      # lookups refresh the stamp
            reg.save(path)
        assert reg.get("dead", {}, "test:v") is None
        assert reg.get("live", {}, "test:v") == {"unroll": 8}
        assert reg.compacted_total == 1
        # the surviving file round-trips with its generation counter
        loaded = TunedRegistry.load(path)
        assert len(loaded) == 1
        assert loaded._generation == reg._generation


def test_registry_put_and_get_warm_refresh_the_stamp():
    """put (even with a worse score) and get_warm hits both count as use;
    aging only bites entries nobody touches."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        reg = TunedRegistry(max_idle_saves=2)
        reg.put("k", {}, "test:v", {"unroll": 8}, 0.1)
        for _ in range(5):
            reg.put("k", {}, "test:v", {"unroll": 1}, 9.0)   # worse: kept
            reg.save(path)
        assert reg.get("k", {}, "test:v") == {"unroll": 8}
        for _ in range(5):
            assert reg.get_warm("k", {}, "test:v") is not None
            reg.save(path)
        assert len(reg) == 1 and reg.compacted_total == 0


def test_registry_foreign_compiler_entries_compacted_on_save():
    """Entries recorded under a different jax/jaxlib can only ever miss:
    save() drops them. Versionless legacy keys make no compiler claim and
    are kept (they still warm-start via the fallback chain)."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        reg = TunedRegistry(max_idle_saves=None)
        reg.put("k", {}, "cpu:x:jax0.1-jaxlib0.1", {"unroll": 8}, 0.1)
        reg.put("k", {}, f"cpu:x:{compiler_version()}", {"unroll": 4}, 0.1)
        reg.put("k", {}, "cpu:x", {"unroll": 2}, 0.1)        # legacy layout
        reg.save(path)
        loaded = TunedRegistry.load(path)
        assert len(loaded) == 2
        assert loaded.get("k", {}, "cpu:x:jax0.1-jaxlib0.1") is None
        assert loaded.get("k", {}, f"cpu:x:{compiler_version()}") is not None
        assert loaded.get("k", {}, "cpu:x") is not None


def test_registry_aging_disabled_keeps_idle_entries():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        reg = TunedRegistry(max_idle_saves=None)
        reg.put("k", {}, "test:v", {"unroll": 8}, 0.1)
        for _ in range(50):
            reg.save(path)
        assert len(TunedRegistry.load(path)) == 1


def test_registry_pre_aging_file_loads_as_freshly_used():
    """Files written before aging existed (no stamps, no meta) must not
    be instantly compacted on the next save."""
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        import json as _json
        with open(path, "w") as f:
            _json.dump({TunedRegistry.key("k", {}, "test:v"):
                        {"point": {"unroll": 8}, "score_s": 0.1}}, f)
        reg = TunedRegistry.load(path)
        assert reg.get("k", {}, "test:v") == {"unroll": 8}
        reg.save(path)                             # one save: still fresh
        assert len(TunedRegistry.load(path)) == 1


def test_registry_records_strategy_provenance():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tuned.json")
        reg = TunedRegistry()
        reg.put("k", {}, "d", {"unroll": 8}, 0.001, strategy="greedy")
        reg.save(path)
        loaded = TunedRegistry.load(path)
        assert loaded.get("k", {}, "d") == {"unroll": 8}
        entry = loaded._table[TunedRegistry.key("k", {}, "d")]
        assert entry["strategy"] == "greedy"


# ------------------------------------------------------------- serve loop
def _serve(max_new_tokens, **tuning):
    """A ServeConfig with program tuning on under the serving defaults,
    and a session of that configuration."""
    from repro.api import TuningSession, serve_tuning_defaults
    from repro.runtime.serve_loop import ServeConfig

    tcfg = dataclasses.replace(serve_tuning_defaults(), enabled=True,
                               **tuning)
    return (ServeConfig(max_new_tokens=max_new_tokens, tuning=tcfg),
            TuningSession(tcfg))


@pytest.mark.parametrize("strategy", ["two_phase", "greedy"])
def test_serve_requests_share_bucketed_prefill_tuner(strategy):
    """Acceptance: prompts of length 120 and 150 (same pow2 bucket, 128)
    must share ONE prefill tuner instead of spawning one per shape."""
    from repro.runtime.serve_loop import generate

    cfg = REGISTRY["deepseek-7b"].reduced()
    serve, session = _serve(4, max_overhead=0.5, strategy=strategy,
                            seq_buckets=True, idle_evict_s=None)
    try:
        for seq in (120, 150):
            batch = {"tokens": jnp.ones((2, seq), jnp.int32)}
            out = generate(cfg, batch, serve, session=session)
            assert out["tokens"].shape == (2, 4)
        stats = out["autotune"]
        prefill_keys = [k for k in stats["kernels"] if "serve_prefill" in k]
        assert len(prefill_keys) == 1, stats["kernels"].keys()
        pf = stats["kernels"][prefill_keys[0]]
        assert pf["strategy"] == strategy
        # both requests' prefill calls landed on the shared tuner
        assert pf["kernel_calls"] == 2
        # the tuner is keyed by the bucket, not either raw length
        (m,) = [m for m in session.coordinator._managed
                if m.name == "serve_prefill"]
        assert m.specialization["seq"] == 128
        # init-time reference measurements are surfaced (and budgeted)
        assert stats["init_spent_s"] > 0
        assert stats["budget_spent_s"] >= stats["init_spent_s"]
    finally:
        session.close()


def test_serve_unbucketed_accumulates_tuners():
    """Control: with bucketing off, the same traffic spawns one prefill
    tuner per exact shape (the leak the lifecycle exists to stop)."""
    from repro.runtime.serve_loop import generate

    cfg = REGISTRY["deepseek-7b"].reduced()
    serve, session = _serve(4, max_overhead=0.5, seq_buckets=False,
                            idle_evict_s=None)
    try:
        for seq in (120, 150):
            batch = {"tokens": jnp.ones((2, seq), jnp.int32)}
            out = generate(cfg, batch, serve, session=session)
    finally:
        session.close()
    stats = out["autotune"]
    prefill_keys = [k for k in stats["kernels"] if "serve_prefill" in k]
    assert len(prefill_keys) == 2


def test_generate_tunes_exactly_the_step_programs():
    """One tuning level: a tuned request registers the prefill and decode
    step programs and nothing else (no kernel plane is built), and serves
    the tokens a request with tuning off serves."""
    from repro.runtime.serve_loop import ServeConfig, generate

    cfg = REGISTRY["deepseek-7b"].reduced()
    tokens = jnp.arange(2 * 24, dtype=jnp.int32).reshape(2, 24) % cfg.vocab
    plain = generate(cfg, {"tokens": tokens}, ServeConfig(max_new_tokens=4))
    serve, session = _serve(4, max_overhead=0.5, idle_evict_s=None)
    try:
        out = generate(cfg, {"tokens": tokens}, serve, session=session)
        names = sorted(m.name for m in session.coordinator._managed)
        assert names == ["serve_decode", "serve_prefill"]
        assert session.plane is None
        assert not any(k.get("plane_managed")
                       for k in out["autotune"]["kernels"].values())
    finally:
        session.close()
    assert (out["tokens"] == plain["tokens"]).all()


def test_serve_kernel_tuning_off_disables_autotune():
    """Tuning off (kernel_tuning="off" wins over enabled=True): no tuners
    register, and no "autotune" stats block (the CLIs key their report
    off its absence)."""
    from repro.runtime.serve_loop import generate

    cfg = REGISTRY["deepseek-7b"].reduced()
    serve, session = _serve(2, kernel_tuning="off")
    try:
        out = generate(cfg, {"tokens": jnp.ones((1, 8), jnp.int32)}, serve,
                       session=session)
        assert session.coordinator._managed == []
    finally:
        session.close()
    assert out["tokens"].shape == (1, 2)
    assert "autotune" not in out


def test_serve_idle_tuner_evicted_between_requests():
    """Acceptance: a tuner idle past the eviction horizon is unregistered
    at the next request's lifecycle pass, its evaluator closure released."""
    from repro.runtime.serve_loop import generate

    import time

    cfg = REGISTRY["deepseek-7b"].reduced()
    serve, session = _serve(4, max_overhead=0.5, seq_buckets=True,
                            idle_evict_s=None)
    coordinator = session.coordinator
    batch = {"tokens": jnp.ones((2, 12), jnp.int32)}
    try:
        generate(cfg, batch, serve, session=session)
        managed_before = list(coordinator._managed)
        assert managed_before
        # the server goes quiet: shrink the horizon so the idle gap
        # between requests crosses it, then run the next lifecycle pass
        coordinator.lifecycle.idle_evict_s = 1e-6
        time.sleep(0.002)
        retired = coordinator.sweep()
        assert set(retired) == set(managed_before)
        for m in retired:
            assert m.state is TunerState.RETIRED
            assert m.tuner.evaluator.make_args is None
        assert coordinator.stats()["n_kernels"] == 0
        assert coordinator.stats()["lifecycle"]["retired"] == len(retired)
        # traffic returning later re-registers cleanly (warm from registry)
        out = generate(cfg, batch, serve, session=session)
        assert out["tokens"].shape == (2, 4)
    finally:
        session.close()
