"""One run of one cell: set-up, a measured window, the check, the metrics.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the names in ``BENCHMARK.json``:

- ``configs[].file``: the configuration's sizes, with ``reference``
  naming its family module ``chipbench/reference/<family>.py`` (plain
  reference, weight tree, work counts) and ``chipbench/adapters/
  <family>.py`` (the program's model configuration for it);
- ``chipbench/traffic/<traffic>.json``: the mix (see ``traffic.py``);
- ``chipbench/cells/<workload>.json``: the correctness check's sample
  size (served tokens) and the limit of each number it compares;
- ``chipbench/metrics/<metric>.py``: a ``read(run)`` that returns the
  metric's value, or None where there is nothing to read.

The system under test is served through its own entry,
``repro.runtime.serve_loop.generate``, one request at a time (a closed
loop of one client), with one ``TuningSession`` built from the mix's
tuning settings and no registry, so every run starts cold.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
REQUEST = "chipbench.request"
BETWEEN = "chipbench.between_requests"


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = sorted(values)
    return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)]


def _load_json(path: str) -> dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str) -> Any:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    mix: dict[str, Any]
    check: dict[str, Any]
    family: Any
    adapter: Any
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]


def load_cell(workload: str, bench: dict[str, Any] | None = None,
              root: str = ROOT) -> Cell:
    """The cell named ``workload`` and every file it names."""
    if bench is None:
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    fam = config["reference"]
    applies = [m for m in bench["end_to_end"] + bench["per_layer"]
               if workload in m.get("workloads", [workload])]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        mix=_load_json(os.path.join(BENCH_DIR, "traffic",
                                    w["traffic"] + ".json")),
        check=_load_json(os.path.join(BENCH_DIR, "cells", workload + ".json")),
        family=importlib.import_module(f"chipbench.reference.{fam}"),
        adapter=importlib.import_module(f"chipbench.adapters.{fam}"),
        end_to_end=[m for m in applies if m in bench["end_to_end"]],
        per_layer=[m for m in applies if m in bench["per_layer"]],
    )


def read_metrics(entries: list[dict[str, Any]], run: "Run") -> dict[str, Any]:
    """Each metric's reader applied to the run; metrics that find nothing
    to read are left out."""
    out = {}
    for m in entries:
        reader = _load_module(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py"),
                              "chipbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    peak: dict[str, Any]
    setup_s: float
    window_s: float
    requests: list[dict[str, Any]]
    compiles: int
    tuning_before: dict[str, Any] | None
    tuning_after: dict[str, Any] | None
    trace: dict[str, Any] | None


class _Compiles:
    """Counts XLA executables built or loaded from the compile cache while
    ``active`` (JAX's backend-compile event covers both)."""

    def __init__(self) -> None:
        import jax
        from jax._src import dispatch

        self.active = False
        self.count = 0
        self.misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw: Any) -> None:
        if self.active and event == self._event:
            self.count += 1

    def _on_event(self, event: str, **kw: Any) -> None:
        if self.active and event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class _FirstToken:
    """The prefill step as ``generate`` calls it, timed to the moment its
    logits (so the first token) are ready on the device."""

    def __init__(self, handle: Any, sink: dict[str, float]) -> None:
        self._handle = handle
        self._sink = sink

    def __getattr__(self, name: str) -> Any:
        return getattr(self._handle, name)

    def __call__(self, *args: Any) -> Any:
        import jax

        out = self._handle(*args)
        jax.block_until_ready(out[0])
        self._sink["t_first"] = time.perf_counter()
        return out


def _session(mix: dict[str, Any], sink: dict[str, float],
             handles: list[Any]) -> tuple[Any, Any]:
    """The session and the tuning configuration every request is served
    with."""
    from repro.api import TuningSession, serve_tuning_defaults

    tcfg = dataclasses.replace(serve_tuning_defaults(), registry_path=None,
                               **mix["tuning"])
    if not tcfg.tune_program:
        raise ValueError(
            "the first token is timed at the session's serve_prefill "
            "step; a mix without program tuning has no such step to time")
    session = TuningSession(tcfg)
    register = session.register

    def timed_register(name: str, *a: Any, **kw: Any) -> Any:
        handle = register(name, *a, **kw)
        if handle not in handles:
            handles.append(handle)
        return _FirstToken(handle, sink) if name == "serve_prefill" else handle

    session.register = timed_register
    return session, tcfg


def prime_compile_cache(handles: list[Any], k: int) -> int:
    """Compile each program tuner's next ``k`` proposals into JAX's
    persistent compile cache, leaving the tuners as they were.

    The tuners compile a variant lazily, on its first measured call in
    the window. Without this, that is a full compile in a fresh checkout
    and a cache load once an earlier run has compiled it, so early runs
    would read slower than later ones. Primed, every run loads the
    variants its window reaches, as a deployment with a warm cache does.
    Returns the number of programs compiled or loaded.
    """
    n = 0
    for h in handles:
        comp, make_args = h.tuner.compilette, h.tuner.evaluator.make_args
        if make_args is None:       # a converged tuner released its inputs
            continue
        for point in h.tuner.explorer.peek(k):
            fn = comp._generate(dict(point), **h.specialization)
            fn.lower(*make_args()).compile()
            n += 1
    return n


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_proc0: float, peak: dict[str, Any],
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr),
        keep_sample: bool = False) -> dict[str, Any]:
    """Set up, serve for ``seconds``, check, and read the metrics.

    Returns the result object without ``device``; the caller adds it.
    ``keep_sample`` adds the checked requests' prompts and served tokens
    (``sample``), for reading a control on the same requests, and their
    gaps (``gaps``).
    """
    import jax
    import numpy as np

    from chipbench import check, weights
    from chipbench.trace import SLICE, load, reduce
    from chipbench.traffic import ClosedLoop
    from repro.runtime.serve_loop import ServeConfig, generate

    cfg, mix, fam = cell.config, cell.mix, cell.family
    model_cfg = cell.adapter.program_config(cfg)
    loop = ClosedLoop(mix, cfg["vocab_size"], seed)
    compiles = _Compiles()

    t = time.perf_counter()
    params = weights.draw(fam.param_specs(cfg), seed, model_cfg.param_dtype)
    log(f"weights: {sum(a.nbytes for a in jax.tree.leaves(params))} bytes "
        f"in {time.perf_counter() - t} s")
    sink: dict[str, float] = {}
    handles: list[Any] = []
    session, tcfg = _session(mix, sink, handles)

    def serve_one(index: int, tokens: np.ndarray,
                  new_tokens: int) -> dict[str, Any]:
        sink.clear()
        rec: dict[str, Any] = {"index": index, "batch": tokens.shape[0],
                               "prompt_len": tokens.shape[1],
                               "new_tokens": new_tokens}
        serve = ServeConfig(max_new_tokens=new_tokens, tuning=tcfg)
        rec["t_start"] = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(REQUEST):
                out = generate(model_cfg, {"tokens": tokens, "params": params},
                               serve, session=session)
        except Exception as e:      # a failed request counts, never hides
            rec.update(t_end=time.perf_counter(), ok=False, error=repr(e))
            log(f"request {index} failed: {e!r}")
            return rec
        rec["t_end"] = time.perf_counter()
        if "t_first" not in sink:
            raise RuntimeError("generate served a request without calling "
                               "the session's serve_prefill step")
        served = np.asarray(out["tokens"])
        rec.update(t_first=sink["t_first"], prefill_s=out["prefill_s"],
                   decode_s=out["decode_s"], prompt=tokens, served=served,
                   ok=bool(out["logits_finite"]) and served.shape == (
                       tokens.shape[0], new_tokens))
        return rec

    t = time.perf_counter()
    for tokens, new in loop.warmup():
        rec = serve_one(-1, tokens, new)
        if not rec["ok"]:
            raise RuntimeError(f"warm-up request failed: {rec}")
    log(f"warm-up of {sorted(loop.block)} in {time.perf_counter() - t} s")
    t = time.perf_counter()
    n = prime_compile_cache(handles, int(mix["prime_variants"]))
    log(f"{n} tuner variants compiled or loaded in {time.perf_counter() - t} s")

    stats0 = session.stats()
    records: list[dict[str, Any]] = []
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    slice_span = None
    traced = 0
    compiles.active = True
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_proc0
    requests = loop.requests()
    while True:
        with jax.profiler.TraceAnnotation(BETWEEN):
            now = time.perf_counter()
            if now - t_w0 >= seconds:
                break
            index, tokens, new = next(requests)
            if trace_dir and slice_span is None and traced == 0 and (
                    now - t_w0 >= mix["trace_after_frac"] * seconds):
                jax.profiler.start_trace(trace_dir)
                slice_span = jax.profiler.TraceAnnotation(SLICE)
                slice_span.__enter__()
        records.append(serve_one(index, tokens, new))
        if slice_span is not None:
            traced += 1
            if traced >= mix["trace_requests"]:
                slice_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                slice_span = None
    t_w1 = records[-1]["t_end"] if records else time.perf_counter()
    compiles.close()
    if slice_span is not None:
        slice_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    stats1 = session.stats()
    devices = jax.devices()[:cell.chips]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    log(f"window: {len(records)} requests in {t_w1 - t_w0} s; "
        f"{compiles.count} executables built or loaded "
        f"({compiles.misses} compile-cache misses); tuning "
        f"{stats1['regenerations'] - stats0['regenerations']} regenerations, "
        f"{stats1['swaps'] - stats0['swaps']} swaps, gen "
        f"{stats1['gen_spent_s'] - stats0['gen_spent_s']} s")

    failed = sum(1 for r in records if not r["ok"])
    finished = [r for r in records if r["ok"]]
    if finished:
        tpot = sorted((r["t_end"] - r["t_first"]) / (r["new_tokens"] - 1)
                      for r in finished)
        ttft = sorted(r["t_first"] - r["t_start"] for r in finished)
        log("ms per output token, highest 12: "
            + " ".join(f"{1e3 * v:.2f}" for v in tpot[-12:][::-1])
            + f"; median {1e3 * tpot[len(tpot) // 2]:.2f}")
        log("ms to first token, highest 12: "
            + " ".join(f"{1e3 * v:.2f}" for v in ttft[-12:][::-1]))

    trace_summary = None
    if trace_dir:
        t = time.perf_counter()
        trace_summary = reduce(load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t} s: "
            f"idle by cause {trace_summary['idle_by_cause']}")

    # the program's state, compiled programs included, goes before the
    # reference runs on the chip: the tuners' evaluators hold the last
    # request's inputs and KV cache
    session.close()
    handles.clear()
    del session, serve_one
    gc.collect()
    jax.clear_caches()
    live = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    log(f"device bytes in use with the program freed: {max(live)}")

    t = time.perf_counter()
    picked = [finished[i] for i in check.sample(
        finished, int(cell.check["sample_tokens"]), seed)]
    sample = [(r["prompt"], r["served"]) for r in picked]
    g = check.gaps(fam, params, cfg, sample)
    values = check.numbers(g)
    log(f"reference check of {len(picked)} requests ({g.size} served "
        f"tokens) in {time.perf_counter() - t} s: {values}")
    del params
    held, numbers = check.judge(values, cell.check["limits"])
    numbers["failed"] = {"value": failed, "limit": 0}
    correct = bool(records) and failed == 0 and held

    for r in records:
        r.pop("prompt", None)
        r.pop("served", None)
    result_run = Run(cell=cell, peak=peak, setup_s=setup_s,
                     window_s=t_w1 - t_w0, requests=finished,
                     compiles=compiles.count, tuning_before=stats0,
                     tuning_after=stats1, trace=trace_summary)
    result: dict[str, Any] = {
        "correct": correct, "attempted": len(records), "failed": failed,
        "metrics": read_metrics(cell.per_layer if trace else cell.end_to_end,
                                result_run),
        "memory_peak_bytes": max(mem) if mem else 0,
    }
    if trace_summary is not None:
        result["trace"] = trace_summary
    if keep_sample:
        result["sample"] = sample
        result["gaps"] = g
    result["check"] = numbers
    return result
