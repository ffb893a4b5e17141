"""PR-5 front door: repro.tune / repro.tuned / repro.TuningSession.

Round-trip suite for the session API: @tuned convergence on the
VirtualClock (no sleeps), config parity across programmatic / env /
flags construction, stats parity between the session path and the
equivalent PR-4 coordinator wiring, the close()/scope() re-entrancy
regression, the decode_attention plane kernel, the generation-cache
byte bound, and the deprecated-constructor import lint.
"""

import argparse
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.api import TuningConfig, TuningSession
from repro.configs import REGISTRY
from repro.core import (
    Compilette,
    DEFAULT_ENTRY_BYTES,
    GeneratedKernel,
    GenerationCache,
    Param,
    RegenerationPolicy,
    TPU_V5E,
    TunedRegistry,
    VirtualClock,
    VirtualClockEvaluator,
    product_space,
)
from repro.kernels import get_catalog
from repro.runtime.coordinator import TuningCoordinator
from repro.runtime.kernel_plane import active_plane
from repro.runtime.lifecycle import TunerState

GEN_COST = 0.002


def unroll_space():
    return product_space([Param("unroll", (1, 2, 4, 8), phase=1)])


def unroll_cost(point) -> float:
    return 0.010 / point["unroll"]


def make_session(clock, **cfg_overrides) -> TuningSession:
    cfg = TuningConfig(max_overhead=1.0, invest=0.5, pump_every=1,
                       **cfg_overrides)
    return TuningSession(cfg, clock=clock, device="test:v")


def virtual_tuned(session, clock, **kwargs):
    """A @tuned virtual kernel: calls burn simulated time by point."""

    @session.tune(space=unroll_space(), jit=False, gen_cost_s=GEN_COST,
                  evaluator=VirtualClockEvaluator(
                      clock, score_fn=lambda f: unroll_cost(f.point)),
                  **kwargs)
    def k(step, *, unroll):
        clock.advance(0.010 / unroll)
        return step

    return k


# ------------------------------------------------------------- @tuned core
def test_tuned_function_converges_under_virtual_clock():
    """Acceptance: the decorator wraps a callable into a managed handle
    that reaches the known optimum deterministically — the application
    only ever calls its own function."""
    clock = VirtualClock()
    session = make_session(clock)
    k = virtual_tuned(session, clock)
    for step in range(300):
        k(step)
        if k.handle is not None and k.handle.tuner.explorer.finished:
            break
    assert k.best_point == {"unroll": 8}
    s = k.stats()
    assert s["n_explored"] == 4
    assert s["swaps"] >= 1
    # double-buffered by default: the budget paid, the hot path never did
    assert s["gen_spent_s"] > 0 and s["gen_stall_s"] == 0.0
    # the swapped-in active function serves the best variant
    assert k.active_fn is k.handle.active_fn
    session.close()


def test_tuned_stats_identical_to_pr4_wiring():
    """Acceptance: @tuned through the session produces bit-identical
    stats() accounting to the equivalent explicit PR-4 wiring
    (TuningCoordinator.register of a hand-built compilette)."""
    calls = 60

    # --- session front door ---------------------------------------------
    clock_a = VirtualClock()
    session = make_session(clock_a)
    ka = virtual_tuned(session, clock_a, name="k")
    for step in range(calls):
        ka(step)

    # --- PR-4 wiring ------------------------------------------------------
    clock_b = VirtualClock()
    coord = TuningCoordinator(
        policy=RegenerationPolicy(max_overhead_frac=1.0, invest_frac=0.5),
        device="test:v", clock=clock_b, pump_every=1,
        async_generation=True, prefetch=1)

    def gen(point, **sp):
        def fn(*args):
            clock_b.advance(unroll_cost(point))
            return args[0] if args else None
        fn.point = dict(point)
        return fn

    comp = Compilette("k", unroll_space(), gen, gen_cost_s=GEN_COST)
    kb = coord.register(
        "k", comp,
        VirtualClockEvaluator(clock_b,
                              score_fn=lambda f: unroll_cost(f.point)),
        specialization={})
    for step in range(calls):
        kb(step)
        coord.maybe_pump()

    sa, sb = ka.stats(), kb.stats()
    for key in ("strategy", "kernel_calls", "regenerations", "swaps",
                "gen_spent_s", "gen_stall_s", "eval_spent_s", "gained_s",
                "reference_score_s", "active_score_s", "best_point",
                "best_score_s", "n_explored", "exploration_finished"):
        assert sa[key] == sb[key], key
    # aggregate rollups agree too (same budget arithmetic on both paths)
    agg_a, agg_b = session.stats(), coord.stats()
    for key in ("regenerations", "swaps", "gen_spent_s", "gen_stall_s",
                "eval_spent_s", "budget_spent_s", "gained_s", "busy_s"):
        assert agg_a[key] == agg_b[key], key
    session.close()
    coord.close()


def test_tuned_spec_from_buckets_handles():
    """spec_from keys separate handles per run-time-constant cell, with
    shape-like keys pow2-bucketed exactly like the kernel plane."""
    clock = VirtualClock()
    session = make_session(clock)

    @session.tune(space=unroll_space(), jit=False, gen_cost_s=GEN_COST,
                  evaluator=VirtualClockEvaluator(
                      clock, score_fn=lambda f: unroll_cost(f.point)),
                  spec_from=lambda step, seq: {"seq": seq})
    def k(step, seq, *, unroll):
        clock.advance(unroll_cost({"unroll": unroll}))
        return step

    k(0, 120)
    k(0, 150)          # same 128 bucket: shares the first handle
    assert len(k.handles()) == 1
    assert k.handle.specialization == {"seq": 128}
    k(0, 40)           # 32 bucket: its own handle
    assert len(k.handles()) == 2
    session.close()


def test_module_level_front_door():
    """repro.tune/repro.tuned/default_session round-trip."""
    clock = VirtualClock()
    session = make_session(clock)
    old = repro.set_default_session(session)
    try:
        @repro.tuned(space=unroll_space(), jit=False, gen_cost_s=GEN_COST,
                     evaluator=VirtualClockEvaluator(
                         clock, score_fn=lambda f: unroll_cost(f.point)))
        def k(step, *, unroll):
            clock.advance(unroll_cost({"unroll": unroll}))
            return step

        k(0)
        assert k.session is session
        assert repro.default_session() is session
    finally:
        repro.set_default_session(old)
        session.close()


# ----------------------------------------------------------------- configs
def test_config_from_env_flags_programmatic_identical():
    """from_flags == from_env == programmatic for the full knob set."""
    base = TuningConfig(enabled=False)
    env = {
        "REPRO_TUNE_AUTOTUNE": "1",
        "REPRO_TUNE_STRATEGY": "greedy",
        "REPRO_TUNE_MAX_OVERHEAD": "0.5",
        "REPRO_TUNE_INVEST": "0.25",
        "REPRO_TUNE_KERNEL_TUNING": "both",
        "REPRO_TUNE_STRATEGIES": "matmul=greedy,attention=random",
        "REPRO_TUNE_REGISTRY_PATH": "/tmp/api_r.json",
        "REPRO_TUNE_SLO_S": "0.05",
        "REPRO_TUNE_SLO_QUANTILE": "0.99",
        "REPRO_TUNE_SEQ_BUCKETS": "0",
        "REPRO_TUNE_ASYNC_GENERATION": "false",
        "REPRO_TUNE_PREFETCH": "3",
    }
    cfg_env = TuningConfig.from_env(env, base=base)

    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser, base=base)
    args = parser.parse_args([
        "--autotune", "--strategy", "greedy", "--tune-overhead", "0.5",
        "--tune-invest", "0.25", "--kernel-tuning", "both",
        "--kernel-strategy", "matmul=greedy",
        "--kernel-strategy", "attention=random",
        "--registry", "/tmp/api_r.json", "--slo", "0.05",
        "--slo-quantile", "0.99", "--no-seq-buckets", "--sync-generation",
        "--prefetch", "3",
    ])
    cfg_flags = TuningConfig.from_flags(args, base=base)

    cfg_prog = TuningConfig(
        enabled=True, strategy="greedy",
        strategies={"matmul": "greedy", "attention": "random"},
        max_overhead=0.5, invest=0.25, registry_path="/tmp/api_r.json",
        slo_s=0.05, slo_quantile=0.99, seq_buckets=False,
        async_generation=False, prefetch=3, kernel_tuning="both")
    assert cfg_env == cfg_flags == cfg_prog
    # the session classmethods accept the same inputs
    s = TuningSession.from_env(env, base=base, clock=VirtualClock())
    assert s.config == cfg_prog
    s.close()


def test_from_flags_inherits_base_strategies_when_flag_absent():
    """Review fix: no --kernel-strategy on the command line must keep the
    base config's per-kernel overrides, like every other flag default."""
    base = TuningConfig(enabled=False, strategies={"matmul": "greedy"})
    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser, base=base)
    cfg = TuningConfig.from_flags(parser.parse_args([]), base=base)
    assert cfg.strategies == {"matmul": "greedy"}
    # an explicit flag still overrides the base
    cfg2 = TuningConfig.from_flags(
        parser.parse_args(["--kernel-strategy", "attention=random"]),
        base=base)
    assert cfg2.strategies == {"attention": "random"}


def test_from_env_bad_strategies_raise_value_error():
    """Review fix: env parsing must follow the env contract (ValueError),
    not the CLI parser's SystemExit."""
    with pytest.raises(ValueError, match="kernel strategies"):
        TuningConfig.from_env(
            {"REPRO_TUNE_STRATEGIES": "matmul=not_a_strategy"})
    with pytest.raises(ValueError, match="kernel strategies"):
        TuningConfig.from_env({"REPRO_TUNE_STRATEGIES": "typo_kernel=greedy"})


def test_config_validation_fails_fast():
    with pytest.raises(ValueError, match="kernel_tuning"):
        TuningConfig(kernel_tuning="bogus")
    with pytest.raises(ValueError, match="budget_from"):
        TuningConfig(budget_from="idle")
    with pytest.raises(ValueError, match="REPRO_TUNE_TYPO"):
        TuningConfig.from_env({"REPRO_TUNE_TYPO": "1"})
    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser)
    args = parser.parse_args(["--slo-quantile", "0.99"])
    with pytest.raises(SystemExit):   # quantile gate needs an SLO
        TuningConfig.from_flags(args)


def test_config_refuses_process_compile_backend():
    """Compiles stay in the process that holds the chip: a child could
    not load the TPU library beside it, so the backend does not exist."""
    with pytest.raises(ValueError, match="compile_backend must be one of"):
        TuningConfig(compile_backend="process")
    with pytest.raises(ValueError, match="compile_backend"):
        TuningConfig.from_env({"REPRO_TUNE_COMPILE_BACKEND": "process"})
    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser)
    with pytest.raises(SystemExit):
        parser.parse_args(["--compile-backend", "process"])


# -------------------------------------------- config round-trip properties
# one random assignment of every flag-covered knob; slo_quantile is
# normalized onto slo_s (from_flags rejects a quantile without an SLO)
_KNOB_ASSIGNMENTS = st.tuples(
    st.booleans(),                                          # enabled
    st.sampled_from(["two_phase", "random", "greedy"]),     # strategy
    st.sampled_from(["off", "program", "kernel", "both"]),  # kernel_tuning
    st.dictionaries(                                        # strategies
        st.sampled_from(["matmul", "attention", "rmsnorm"]),
        st.sampled_from(["two_phase", "random", "greedy"]),
        min_size=0, max_size=3),
    st.floats(min_value=0.0, max_value=1.0),                # max_overhead
    st.floats(min_value=0.0, max_value=1.0),                # invest
    st.sampled_from([None, "/tmp/api_prop_reg.json"]),      # registry_path
    st.sampled_from([None, 0.01, 0.25]),                    # slo_s
    st.sampled_from([None, 0.5, 0.99]),                     # slo_quantile
    st.booleans(),                                          # seq_buckets
    st.booleans(),                                          # async_generation
    st.integers(min_value=0, max_value=4),                  # prefetch
)


@settings(max_examples=25)
@given(_KNOB_ASSIGNMENTS)
def test_config_round_trips_for_random_knobs(knobs):
    """Property: programmatic == from_env == from_flags for ANY knob
    assignment, not just the single hand-picked example above."""
    (enabled, strategy, kernel_tuning, strategies, max_overhead, invest,
     registry_path, slo_s, slo_quantile, seq_buckets, async_generation,
     prefetch) = knobs
    if slo_s is None:
        slo_quantile = None
    strategies = strategies or None       # {} and None parse identically

    base = TuningConfig(enabled=False)
    cfg_prog = TuningConfig(
        enabled=enabled, strategy=strategy, kernel_tuning=kernel_tuning,
        strategies=strategies, max_overhead=max_overhead, invest=invest,
        registry_path=registry_path, slo_s=slo_s, slo_quantile=slo_quantile,
        seq_buckets=seq_buckets, async_generation=async_generation,
        prefetch=prefetch)

    env = {
        "REPRO_TUNE_AUTOTUNE": "1" if enabled else "0",
        "REPRO_TUNE_STRATEGY": strategy,
        "REPRO_TUNE_KERNEL_TUNING": kernel_tuning,
        "REPRO_TUNE_STRATEGIES": ",".join(
            f"{k}={v}" for k, v in (strategies or {}).items()),
        "REPRO_TUNE_MAX_OVERHEAD": repr(max_overhead),
        "REPRO_TUNE_INVEST": repr(invest),
        "REPRO_TUNE_REGISTRY_PATH": registry_path or "",
        "REPRO_TUNE_SLO_S": "" if slo_s is None else repr(slo_s),
        "REPRO_TUNE_SLO_QUANTILE": (
            "" if slo_quantile is None else repr(slo_quantile)),
        "REPRO_TUNE_SEQ_BUCKETS": "1" if seq_buckets else "0",
        "REPRO_TUNE_ASYNC_GENERATION": "true" if async_generation else "no",
        "REPRO_TUNE_PREFETCH": str(prefetch),
    }
    assert TuningConfig.from_env(env, base=base) == cfg_prog

    argv = []
    if enabled:
        argv.append("--autotune")
    argv += ["--strategy", strategy, "--kernel-tuning", kernel_tuning]
    for k, v in (strategies or {}).items():
        argv += ["--kernel-strategy", f"{k}={v}"]
    argv += ["--tune-overhead", repr(max_overhead),
             "--tune-invest", repr(invest),
             "--prefetch", str(prefetch)]
    if registry_path is not None:
        argv += ["--registry", registry_path]
    if slo_s is not None:
        argv += ["--slo", repr(slo_s)]
    if slo_quantile is not None:
        argv += ["--slo-quantile", repr(slo_quantile)]
    argv.append("--seq-buckets" if seq_buckets else "--no-seq-buckets")
    if not async_generation:
        argv.append("--sync-generation")
    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser, base=base)
    assert TuningConfig.from_flags(parser.parse_args(argv), base=base) \
        == cfg_prog


@settings(max_examples=25)
@given(st.sampled_from(["BUDGET", "OVERHEAD", "MAX_OVERHED", "STRATGY",
                        "PUMP", "CACHE", "EVICT"]),
       st.integers(min_value=0, max_value=99))
def test_config_from_env_unknown_keys_always_raise(stem, suffix):
    """Property: a typo'd REPRO_TUNE_* knob never parses silently, even
    next to perfectly valid keys."""
    env = {
        "REPRO_TUNE_STRATEGY": "greedy",          # valid
        f"REPRO_TUNE_{stem}{suffix}": "1",        # never a field name
    }
    with pytest.raises(ValueError, match="unknown tuning variable"):
        TuningConfig.from_env(env)


# -------------------------------------------------------- close/scope fix
def test_session_close_exactly_once_under_reentrant_scopes():
    """Regression (PR-5 satellite): nested scope() exits and repeated
    close() calls flush the registry and stop the async generator ONCE."""
    clock = VirtualClock()
    cfg = TuningConfig(max_overhead=1.0, invest=0.5, pump_every=1)
    session = TuningSession(cfg, clock=clock, device="test:v",
                            close_on_scope_exit=True)
    counts = {"save": 0, "shutdown": 0}
    real_save = session.coordinator.save_registry
    real_shutdown = session.coordinator.generator.shutdown

    def save_spy(path=None):
        counts["save"] += 1
        real_save(path)

    def shutdown_spy():
        counts["shutdown"] += 1
        real_shutdown()

    session.coordinator.save_registry = save_spy
    session.coordinator.generator.shutdown = shutdown_spy

    with session.scope():
        with session.scope():      # re-entrant: a request inside a scope
            pass
        assert not session.closed  # inner exit must NOT close
    assert session.closed          # outermost exit closed...
    assert counts == {"save": 1, "shutdown": 1}
    session.close()                # ...and close() is now a no-op
    session.close()
    assert counts == {"save": 1, "shutdown": 1}
    with pytest.raises(RuntimeError):
        with session.scope():
            pass


def test_session_close_flushes_registry(tmp_path):
    path = str(tmp_path / "tuned.json")
    clock = VirtualClock()
    session = make_session(clock, registry_path=path)
    k = virtual_tuned(session, clock, name="flushk")
    for step in range(300):
        k(step)
        if k.handle is not None and k.handle.tuner.explorer.finished:
            break
    session.close()
    assert os.path.exists(path)
    loaded = TunedRegistry.load(path)
    assert loaded.get("flushk", {}, session.coordinator.device) == \
        {"unroll": 8}


# ------------------------------------------------------- deprecation shims
def test_legacy_config_fields_alias_into_tuning():
    from repro.runtime.serve_loop import ServeConfig
    from repro.runtime.train_loop import TrainLoopConfig

    serve = ServeConfig(autotune=True, tune_strategy="greedy",
                        kernel_strategies={"matmul": "greedy"},
                        tune_max_overhead=0.3)
    assert serve.tuning.enabled and serve.autotune
    assert serve.tuning.strategy == "greedy" == serve.tune_strategy
    assert serve.tuning.strategies == {"matmul": "greedy"}
    assert serve.tuning.max_overhead == 0.3
    serve.tune_slo_s = 0.05            # property writes reach the config
    assert serve.tuning.slo_s == 0.05
    # serving-grade defaults survive the collapse
    assert serve.tuning.budget_from == "busy" and serve.tuning.charge_init
    with pytest.raises(TypeError, match="unexpected"):
        ServeConfig(bogus_knob=1)

    loop = TrainLoopConfig(autotune=True, tune_async=False,
                           tune_prefetch=2)
    assert loop.tuning.enabled
    assert loop.tuning.async_generation is False
    assert loop.tuning.prefetch == 2 == loop.tune_prefetch
    assert loop.tuning.budget_from == "wall"
    assert loop.tuning.seq_buckets is False   # train-grade defaults
    with pytest.raises(TypeError, match="unexpected"):
        TrainLoopConfig(bogus_knob=1)


def test_make_serve_coordinator_shim_warns_and_matches_session_path():
    """The deprecated constructor warns, and a request through it rolls
    up stats identically in structure to the session front door."""
    from repro.runtime.serve_loop import (
        ServeConfig, generate, make_serve_coordinator)

    cfg = REGISTRY["deepseek-7b"].reduced()
    serve = ServeConfig(max_new_tokens=4, autotune=True,
                        tune_max_overhead=0.5, kernel_tuning="both",
                        kernel_strategies={"attention": "greedy"},
                        seq_buckets=True, idle_evict_s=None)
    with pytest.warns(DeprecationWarning, match="TuningSession"):
        coordinator = make_serve_coordinator(serve)
    # the shim's coordinator is itself session-owned (one front door)
    assert isinstance(getattr(coordinator, "_session", None), TuningSession)

    def batch():
        return {"tokens": jnp.ones((2, 24), jnp.int32)}

    out_shim = generate(cfg, batch(), serve, coordinator=coordinator)
    session = TuningSession(serve.tuning)
    try:
        out_sess = generate(cfg, batch(), serve, session=session)
        for out in (out_shim, out_sess):
            a = out["autotune"]
            # identical rollup arithmetic: per-kernel sums + tombstone
            # reconcile exactly with the aggregate on both paths
            for f in ("gen_spent_s", "gen_stall_s", "eval_spent_s"):
                rollup = (sum(k[f] for k in a["kernels"].values())
                          + a["retired_accounts"][f])
                assert rollup == pytest.approx(a[f]), f
        a, b = out_shim["autotune"], out_sess["autotune"]
        assert set(a["kernels"]) == set(b["kernels"])
        for name in a["kernels"]:
            assert (a["kernels"][name]["strategy"]
                    == b["kernels"][name]["strategy"]), name
        # hierarchical registration includes the PR-5 decode kernel
        assert "decode_attention" in a["kernels"]
    finally:
        session.close()
        TuningSession.adopt(coordinator).close()


# ------------------------------------------------------- decode_attention
def test_decode_attention_kernel_matches_oracle():
    """Real backend: any k_chunk variant computes the same attention as
    the single-chunk oracle, and the spec round-trips from live args."""
    from repro.kernels.attention.ops import decode_attention

    spec = {"B": 2, "S": 64, "H": 4, "Hk": 2, "Dh": 16,
            "dtype": "float32"}
    comp = get_catalog().compilette("decode_attention", spec, aot=False)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 1, 4, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 16),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 16),
                          jnp.float32)
    length = jnp.int32(40)
    oracle = decode_attention(q, k, v, length=length, k_chunk=64)
    for point in comp.space.iter_valid():
        kern = comp.generate(point)
        np.testing.assert_allclose(
            np.asarray(kern.fn(q, k, v, length)), np.asarray(oracle),
            rtol=1e-5, atol=1e-5)
    extracted = get_catalog().spec_of("decode_attention", q, k, v, length)
    for kk, vv in spec.items():
        assert extracted[kk] == vv, kk


def test_decode_attention_tunes_per_cache_length_bucket():
    """Satellite acceptance: attach_kernels registers the decode kernel
    keyed per cache-length bucket; each bucket converges to its own
    cost-model optimum and the decode path adopts it at trace time."""
    from repro.models.layers import plane_decode_chunk

    model_cfg = REGISTRY["deepseek-7b"].reduced()
    clock = VirtualClock()
    cfg = TuningConfig(max_overhead=1.0, invest=0.5, pump_every=1)
    session = TuningSession(
        cfg, clock=clock, device="test:v", virtual=(clock, TPU_V5E),
        gen_cost_s=GEN_COST,
        evaluator_factory=lambda c: VirtualClockEvaluator(clock))
    plane = session.attach_kernels(model_cfg, batch=2, seq=24, max_len=300)
    handles = plane.handles("decode_attention")
    assert len(handles) == 1
    (h,) = handles
    assert h.specialization["S"] == 256      # pow2 bucket of 300
    for step in range(2000):
        h(step)
        clock.advance(0.001)   # application work accrues wall budget
        session.pump()
        if h.tuner.explorer.finished:
            break
    assert h.tuner.explorer.finished
    comp = h.tuner.compilette
    expected = min(
        comp.space.iter_valid(),
        key=lambda p: comp.simulate(p, TPU_V5E))
    assert h.tuner.explorer.best_point == expected
    # trace-time adoption: inside the session scope the decode path reads
    # the tuned chunk; outside (or with a program tuner owning the knob)
    # the config default stands
    assert plane_decode_chunk(model_cfg) == model_cfg.decode_k_chunk
    with session.scope():
        assert active_plane() is plane
        assert plane_decode_chunk(model_cfg) == expected["k_chunk"]
    plane.adopt_points = False
    with session.scope():
        assert plane_decode_chunk(model_cfg) == model_cfg.decode_k_chunk
    plane.adopt_points = True
    # a second cache-length cell gets its own handle (own bucket key)
    session.attach_kernels(model_cfg, batch=2, seq=24, max_len=1000)
    assert len(plane.handles("decode_attention")) == 2
    assert {m.specialization["S"]
            for m in plane.handles("decode_attention")} == {256, 1024}


def test_decode_attention_bucket_registry_keys_never_collide():
    """Regression: every cache-length bucket persists under its OWN
    registry key — no max_len pair may alias one entry — and a second
    session warm-starts each bucket from its own best independently."""
    model_cfg = REGISTRY["deepseek-7b"].reduced()
    registry = TunedRegistry()
    max_lens = (300, 1000, 5000)          # buckets 256 / 1024 / 4096

    def run_session():
        clock = VirtualClock()
        cfg = TuningConfig(max_overhead=1.0, invest=0.5, pump_every=1)
        session = TuningSession(
            cfg, clock=clock, device="test:v", registry=registry,
            virtual=(clock, TPU_V5E), gen_cost_s=GEN_COST,
            evaluator_factory=lambda c: VirtualClockEvaluator(clock))
        plane = None
        for max_len in max_lens:
            plane = session.attach_kernels(
                model_cfg, batch=2, seq=24, max_len=max_len)
        handles = plane.handles("decode_attention")
        for step in range(4000):
            for h in handles:
                h(step)
            clock.advance(0.001)
            session.pump()
            if all(h.tuner.explorer.finished for h in handles):
                break
        by_bucket = {h.specialization["S"]: h for h in handles}
        session.close()                   # flushes bests to the registry
        return by_bucket

    cold = run_session()
    assert sorted(cold) == [256, 1024, 4096]

    # distinct buckets -> distinct registry keys (the collision would
    # silently share one tuned point across every cache length); the
    # device part carries the kernel's source hash (satellite: editing
    # ops.py invalidates persisted bests)
    keys = {S: TunedRegistry.key("decode_attention",
                                 dict(h.specialization), h.registry_device)
            for S, h in cold.items()}
    assert len(set(keys.values())) == len(max_lens)
    assert all(":src-" in h.registry_device for h in cold.values())
    # and each key resolves to ITS bucket's best, not a shared one
    for S, h in cold.items():
        assert h.tuner.explorer.finished
        entry = registry.get("decode_attention",
                             dict(h.specialization), h.registry_device)
        assert entry == h.tuner.explorer.best_point, S

    warm = run_session()
    for S, h in warm.items():
        assert h.warm_started, S
        assert h.tuner.explorer.best_point == cold[S].tuner.explorer.best_point


# ------------------------------------------------------- cache byte bound
def _entry(cost: float, size: int | None = None) -> GeneratedKernel:
    meta = {"compiled_in_s": cost}
    if size is not None:
        meta["size_bytes"] = size
    return GeneratedKernel(point={}, fn=lambda *a: None,
                           generation_time_s=cost, specialization={},
                           meta=meta)


def test_generation_cache_byte_bound_evicts_cheapest():
    """Satellite: max_bytes bounds estimated executable residency; the
    victim is still the cheapest-to-regenerate entry in the LRU window."""
    cache = GenerationCache(max_bytes=3000)
    cache.put(("a",), _entry(0.001, 1000))   # cheapest to regenerate
    cache.put(("b",), _entry(0.500, 1000))   # expensive
    cache.put(("c",), _entry(0.002, 1000))
    assert cache.stats()["bytes"] == 3000 and cache.evictions == 0
    cache.put(("d",), _entry(0.100, 1000))   # overflow by bytes
    assert ("a",) not in cache               # cost-weighted victim
    assert ("b",) in cache and ("c",) in cache and ("d",) in cache
    assert cache.stats()["bytes"] == 3000
    assert cache.evictions == 1
    # replacing a key must not double-charge its bytes
    cache.put(("d",), _entry(0.100, 500))
    assert cache.stats()["bytes"] == 2500
    # a lone entry larger than the bound stays (newest never self-evicts)
    small = GenerationCache(max_bytes=10)
    small.put(("x",), _entry(0.1, 1000))
    assert ("x",) in small and small.stats()["bytes"] == 1000
    # entries without a recorded size charge the default estimate
    dflt = GenerationCache(max_bytes=DEFAULT_ENTRY_BYTES)
    dflt.put(("y",), _entry(0.1))
    assert dflt.stats()["bytes"] == DEFAULT_ENTRY_BYTES
    # the count bound keeps working beside the byte bound
    both = GenerationCache(max_entries=2, max_bytes=10**9)
    for i, name in enumerate(("p", "q", "r")):
        both.put((name,), _entry(0.1 * (i + 1), 10))
    assert len(both) == 2 and both.evictions == 1


def test_memory_pressure_shrinks_effective_byte_bound():
    """Satellite: the byte bound follows live device headroom — as free
    device memory shrinks, eviction tightens below the static max_bytes;
    with plenty free, the static bound rules unchanged."""
    free = {"bytes": 10**9}
    cache = GenerationCache(max_bytes=3000,
                            free_memory_fn=lambda: free["bytes"],
                            memory_headroom_frac=0.5)
    for name in ("a", "b", "c"):
        cache.put((name,), _entry(0.1, 1000))
    # plenty free: static bound rules, nothing evicted
    assert len(cache) == 3 and cache.pressure_evictions == 0
    assert cache.stats()["effective_max_bytes"] == 3000
    # device fills up: headroom says only 2000 bytes of cache allowed
    free["bytes"] = 4000
    cache.put(("d",), _entry(0.1, 1000))
    assert cache.stats()["effective_max_bytes"] == 2000
    assert cache.stats()["bytes"] <= 2000
    # evictions forced by PRESSURE (not the static bound) are counted
    assert cache.pressure_evictions > 0
    assert cache.evictions >= cache.pressure_evictions


def test_memory_pressure_static_fallback_when_unreadable():
    """No readable device stats (CPU hosts: free_memory_fn returns None)
    -> the static max_bytes bound applies exactly as before."""
    cache = GenerationCache(max_bytes=2000, free_memory_fn=lambda: None)
    for name in ("a", "b", "c"):
        cache.put((name,), _entry(0.1, 1000))
    assert cache.stats()["effective_max_bytes"] == 2000
    assert len(cache) == 2 and cache.pressure_evictions == 0
    # and with NO static bound either, pressure alone can still bound
    unbounded = GenerationCache(free_memory_fn=lambda: 2000,
                                memory_headroom_frac=0.5)
    for name in ("x", "y", "z"):
        unbounded.put((name,), _entry(0.1, 500))
    assert unbounded.stats()["effective_max_bytes"] == 1000
    assert unbounded.stats()["bytes"] <= 1000
    assert unbounded.pressure_evictions > 0


def test_device_free_memory_bytes_is_none_or_positive():
    """The jax probe degrades to None (static fallback) off-accelerator."""
    from repro.core import device_free_memory_bytes

    free = device_free_memory_bytes()
    assert free is None or free > 0


def test_aot_compile_records_size_estimate():
    """AOT-compiled kernel variants record their executable size for the
    byte-bounded cache (None is legal where the backend reports none)."""
    comp = get_catalog().compilette(
        "rmsnorm", {"N": 64, "d": 32, "dtype": "float32"}, aot=True)
    point = next(iter(comp.space.iter_valid()))
    kern = comp.generate(point)
    assert "size_bytes" in kern.meta
    size = kern.meta["size_bytes"]
    assert size is None or size > 0


# ------------------------------------------------------------------- lint
def test_no_deprecated_constructor_imports():
    """CI satellite, enforced in tier-1 too: src/repro/runtime and
    src/repro/launch must not import the deprecated constructors."""
    tool = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "check_deprecated_imports.py")
    spec = importlib.util.spec_from_file_location("check_deprecated", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.violations() == []


# ------------------------------------------------------------ plane prune
def test_tuned_function_releases_live_args_on_convergence():
    """Converged handles must not keep pinning the last call's arrays."""
    clock = VirtualClock()
    session = make_session(clock)
    k = virtual_tuned(session, clock)
    for step in range(300):
        k(step)
        if k.handle is not None and k.handle.tuner.explorer.finished:
            break
    session.sweep()
    assert k.handle.state is TunerState.CONVERGED
    k(0)   # a call after convergence serves the best fn without pinning
    assert k._live_args == {}
    session.close()
