"""Spans and counters (``repro.core.telemetry``): totals, nesting,
threads, compile attribution; the spans ``generate`` opens; the model
step's named scopes; and the benchmark's readers of the table."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def delta(before, after, name):
    zero = {"s": 0.0, "n": 0}
    a, b = after.get(name, zero), before.get(name, zero)
    return {"s": a["s"] - b["s"], "n": a["n"] - b["n"]}


def busy_wait(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def compile_fresh(tag, n):
    """Compile (and run) one program no earlier call has compiled; its
    input is a host array, so nothing else compiles."""
    jax.jit(lambda x: x * 3.0 + float(hash(tag) % 1000))(np.ones(n))


# ------------------------------------------------------------ the table
def test_span_totals_and_counts():
    before = telemetry.snapshot()
    for _ in range(3):
        with telemetry.span("test.total"):
            busy_wait(0.002)
    d = delta(before, telemetry.snapshot(), "test.total")
    assert d["n"] == 3
    assert 0.006 <= d["s"] < 1.0


def test_nested_spans_count_each_level():
    before = telemetry.snapshot()
    with telemetry.span("test.outer"):
        busy_wait(0.001)
        for _ in range(2):
            with telemetry.span("test.inner"):
                busy_wait(0.001)
    after = telemetry.snapshot()
    outer = delta(before, after, "test.outer")
    inner = delta(before, after, "test.inner")
    assert (outer["n"], inner["n"]) == (1, 2)
    assert inner["s"] < outer["s"]


def test_span_closes_on_exception():
    before = telemetry.snapshot()
    with pytest.raises(ValueError):
        with telemetry.span("test.raises"):
            raise ValueError("boom")
    assert delta(before, telemetry.snapshot(), "test.raises")["n"] == 1
    # the thread's stack is empty again: a compile lands on no span
    before = telemetry.snapshot()
    compile_fresh("after-raise", 3)
    assert delta(before, telemetry.snapshot(), "compile.test.raises")["n"] == 0


def test_traced_decorator_spans_each_call():
    @telemetry.traced("test.traced")
    def f(x, *, k):
        return x + k

    before = telemetry.snapshot()
    assert [f(1, k=2), f(3, k=4)] == [3, 7]
    assert delta(before, telemetry.snapshot(), "test.traced")["n"] == 2


def test_threads_keep_every_update():
    """More threads than cores, a short switch interval: the locked
    update loses no count."""
    threads_n, per_thread = 4 * (os.cpu_count() or 2), 300
    before = telemetry.snapshot()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with telemetry.span("test.threads"):
                    pass
                telemetry.counter("test.thread_counter")

        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    after = telemetry.snapshot()
    assert delta(before, after, "test.threads")["n"] == threads_n * per_thread
    assert (delta(before, after, "test.thread_counter")["n"]
            == threads_n * per_thread)


def test_snapshot_is_a_copy_and_counters_add():
    before = telemetry.snapshot()
    telemetry.counter("test.counter", 2)
    telemetry.counter("test.counter", 3)
    after = telemetry.snapshot()
    assert delta(before, after, "test.counter") == {"s": 0.0, "n": 5}
    after["test.counter"]["n"] = -1
    assert telemetry.snapshot()["test.counter"]["n"] != -1


# ---------------------------------------------------- compile attribution
def test_compile_counted_under_the_innermost_span():
    before = telemetry.snapshot()
    with telemetry.span("test.compile_outer"):
        with telemetry.span("test.compile"):
            compile_fresh("innermost", 4)
    after = telemetry.snapshot()
    d = delta(before, after, "compile.test.compile")
    assert d["n"] == 1 and d["s"] > 0
    assert delta(before, after, "compile.test.compile_outer")["n"] == 0
    assert delta(before, after, "compile.request")["n"] == 0


def test_compile_on_another_thread_stays_with_that_thread():
    """A compile farm worker's compile counts under its own span, not
    under the request the serving thread has open."""
    before = telemetry.snapshot()
    with telemetry.span("serve.request"):
        def worker():
            with telemetry.span("test.worker"):
                compile_fresh("worker", 5)

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    after = telemetry.snapshot()
    assert delta(before, after, "compile.test.worker")["n"] == 1
    assert delta(before, after, "compile.request")["n"] == 0
    assert delta(before, after, "compile.serve.request")["n"] == 0


def test_compile_inside_a_request_counts_for_the_request():
    before = telemetry.snapshot()
    with telemetry.span("serve.request"):
        with telemetry.span("test.in_request"):
            compile_fresh("in-request", 6)
    after = telemetry.snapshot()
    assert delta(before, after, "compile.test.in_request")["n"] == 1
    assert delta(before, after, "compile.request")["n"] == 1


def test_evaluate_waits_for_its_inputs_inside_its_span():
    from repro.core import Evaluator

    x = jnp.ones((8, 8))
    ev = Evaluator(mode="real", real_runs=2, warmup=1,
                   make_args=lambda: (x,))
    before = telemetry.snapshot()
    m = ev.evaluate(jax.jit(lambda a: a @ a))
    after = telemetry.snapshot()
    assert m.n_runs == 3 and m.eval_time_s > 0
    ev_span = delta(before, after, "tuner.evaluate")
    wait = delta(before, after, "tuner.wait_inputs")
    assert ev_span["n"] == 1 and wait["n"] == 1
    assert wait["s"] <= ev_span["s"]


# ------------------------------------------------------------- generate
def test_generate_opens_its_spans_once_each():
    from repro.api import TuningSession, serve_tuning_defaults
    from repro.configs import REGISTRY
    from repro.runtime.serve_loop import ServeConfig, generate

    cfg = REGISTRY["deepseek-7b"].reduced()
    new = 9
    serve = ServeConfig(max_new_tokens=new, tuning=dataclasses.replace(
        serve_tuning_defaults(), enabled=True, max_overhead=0.5,
        idle_evict_s=None))
    session = TuningSession(serve.tuning)
    pumps = []
    pump = session.coordinator.pump
    session.coordinator.pump = lambda: pumps.append(1) or pump()
    try:
        before = telemetry.snapshot()
        out = generate(cfg, {"tokens": jnp.ones((2, 24), jnp.int32)}, serve,
                       session=session)
        after = telemetry.snapshot()
    finally:
        session.close()
    counts = {name: delta(before, after, name)["n"] for name in (
        "serve.request", "serve.setup", "serve.prefill", "serve.cache_widen",
        "serve.decode_step", "serve.decode", "serve.finish", "tuner.pump",
        "tuner.register")}
    assert counts == {
        "serve.request": 1, "serve.setup": 2, "serve.prefill": 1,
        "serve.cache_widen": 1, "serve.decode_step": new - 1,
        "serve.decode": 1, "serve.finish": 1, "tuner.pump": len(pumps),
        "tuner.register": 2}
    assert len(pumps) == (new - 1) // serve.tuning.pump_every
    assert "telemetry" in out["autotune"]
    assert out["autotune"]["telemetry"]["serve.setup"]["n"] >= 2
    assert "tune_init_s" not in out
    assert 0 < out["first_token_s"] < delta(before, after,
                                            "serve.request")["s"]
    # every compile of the request, by span, adds up to compile.request
    by_span = sum(delta(before, after, k)["s"] for k in after
                  if k.startswith(("compile.serve.", "compile.tuner."))
                  and k != "compile.tuner.generate")
    assert by_span == pytest.approx(
        delta(before, after, "compile.request")["s"])


def test_generate_counts_the_prefill_kernel_tiles(monkeypatch):
    """Each served prefill adds its fused attention tiles to
    ``attn.prefill_tiles`` and ``attn.prefill_tiles_run``: zero where the
    chunked jnp path served (here, the CPU), and on a TPU the grid and the
    causal tiles of every (layer, sequence, KV head), at the served
    program's blocks."""
    import types

    from repro.api import TuningSession, serve_tuning_defaults
    from repro.configs import REGISTRY
    from repro.kernels.attention.ops import causal_tiles
    from repro.runtime import serve_loop
    from repro.runtime.serve_loop import ServeConfig, generate

    names = ("attn.prefill_tiles", "attn.prefill_tiles_run")
    cfg = REGISTRY["deepseek-7b"].reduced()
    B, T = 2, 100
    batch = {"tokens": jnp.ones((B, T), jnp.int32)}
    serve = ServeConfig(max_new_tokens=2, tuning=dataclasses.replace(
        serve_tuning_defaults(), enabled=True, idle_evict_s=None))
    session = TuningSession(serve.tuning)
    try:
        before = telemetry.snapshot()
        out = generate(cfg, dict(batch), serve, session=session)
    finally:
        session.close()
    tel = out["autotune"]["telemetry"]
    assert all(name in tel for name in names)
    assert [delta(before, tel, n)["n"] for n in names] == [0, 0]

    monkeypatch.setattr(serve_loop, "_platform", lambda params: "tpu")
    before = telemetry.snapshot()
    generate(cfg, dict(batch), ServeConfig(max_new_tokens=2))
    after = telemetry.snapshot()
    calls = cfg.n_layers * B * cfg.n_kv_heads
    grid, run = causal_tiles(T, T, cfg.attn_q_chunk, cfg.attn_k_chunk)
    assert run < grid
    assert [delta(before, after, n)["n"] for n in names] == \
        [calls * grid, calls * run]
    tuned = types.SimpleNamespace(tuner=types.SimpleNamespace(
        last_served_point={"attn_q_chunk": 64, "attn_k_chunk": 32}))
    grid, run = causal_tiles(T, T, 64, 32)
    assert serve_loop._prefill_tiles(cfg, tuned, None, B, T) == \
        (calls * grid, calls * run)
    windowed = dataclasses.replace(cfg, window=16)
    assert serve_loop._prefill_tiles(windowed, tuned, None, B, T) == (0, 0)


# ------------------------------------------------------- model step scopes
SCOPES = ("embed", "attn.qkv", "attn.rope", "attn.core", "attn.out", "mlp",
          "norm", "head")


def _step_hlo(step):
    from repro.configs import REGISTRY
    from repro.models.model import build_model
    from repro.models.params import init_tree

    # bf16, as served
    cfg = dataclasses.replace(REGISTRY["deepseek-7b"].reduced(),
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: init_tree(
        model.param_defs(), jax.random.PRNGKey(0), cfg.param_dtype))
    B, T, S = 1, 16, 24
    if step == "prefill":
        lowered = jax.jit(model.prefill).lower(
            params, {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32)})
    else:
        cache = tuple(jax.ShapeDtypeStruct(c.shape, c.dtype)
                      for c in model.init_cache_shape(B, S))
        lowered = jax.jit(model.decode_step).lower(
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return lowered.as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_step_programs_carry_the_scope_names(step):
    op_names = re.findall(r'op_name="([^"]*)"', _step_hlo(step))
    scopes = SCOPES + (("attn.kv_update", "cache.read", "cache.write")
                       if step == "decode" else ())
    missing = [s for s in scopes
               if not any(re.search(rf"(^|[/;]){re.escape(s)}/", name)
                          for name in op_names)]
    assert not missing, missing


# --------------------------------------------------- the benchmark's readers
def _reader(name):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(before, after, window_s=10.0):
    return types.SimpleNamespace(
        tuning_before=None if before is None else {"telemetry": before},
        tuning_after=None if after is None else {"telemetry": after},
        window_s=window_s)


BEFORE = {"serve.request": {"s": 5.0, "n": 10},
          "serve.setup": {"s": 0.1, "n": 20},
          "tuner.pump": {"s": 1.0, "n": 40}}
AFTER = {"serve.request": {"s": 15.0, "n": 14},
         "serve.setup": {"s": 0.14, "n": 28},
         "serve.finish": {"s": 0.02, "n": 4},
         "tuner.pump": {"s": 1.3, "n": 60},
         "tuner.register": {"s": 0.01, "n": 8},
         "tuner.wait_inputs": {"s": 0.06, "n": 5},
         "compile.request": {"s": 0.7, "n": 3}}


@pytest.mark.parametrize("name,value", [
    ("tuner.inline_pct", 100.0 * (0.3 + 0.01 - 0.06) / 10.0),
    ("serve.request_compile_s", 0.7),
    ("serve.setup_ms", 1e3 * 0.04 / 4),
    ("serve.finish_ms", 1e3 * 0.02 / 4),
])
def test_reader_takes_the_window_delta(name, value):
    assert _reader(name)(_run(BEFORE, AFTER)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["tuner.inline_pct",
                                  "serve.request_compile_s",
                                  "serve.setup_ms", "serve.finish_ms"])
def test_reader_finds_nothing_without_the_table(name):
    """A program that keeps no table (the stats without ``telemetry``,
    or no session at all) reads None, and a window without a request has
    no per-request time."""
    read = _reader(name)
    assert read(types.SimpleNamespace(
        tuning_before={"regenerations": 0}, tuning_after={"regenerations": 1},
        window_s=10.0)) is None
    assert read(_run(None, None)) is None
    if name.endswith("_ms"):
        assert read(_run(BEFORE, BEFORE)) is None
