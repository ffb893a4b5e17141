"""Spans and counters of the serving path, the tuner and the compile farm.

One process-wide table, keyed by name, holds for each span its total
wall seconds (``time.perf_counter``) and its count, and for each counter
its count. ``snapshot()`` copies it; a reader takes the difference of two
snapshots. Every span also enters ``jax.profiler.TraceAnnotation`` of the
same name, so it sits in a profiler trace on the clock the device planes
are aligned to. It is always on: a span costs one inactive TraceMe, two
clock reads and a locked dict update.

The spans do not feed the tuner's budget: ``TuningAccounts`` stays the
budget's clock (virtual clock included). Spans wrap the same regions on
the wall clock, for the operator.

Spans, and what each brackets:

- ``serve.request``: one ``runtime.serve_loop.generate`` call.
- ``serve.setup``: model and jit-wrapper construction and the prefill
  registration, before the prefill dispatch; a second instance per
  request covers the decode registration before the decode loop.
- ``serve.prefill``: the prefill step from dispatch until its logits are
  ready on the device.
- ``serve.cache_widen``: padding the prefill's KV caches to the decode
  length, through the sync on them.
- ``serve.decode_step``: one decode step's dispatch (and the sync a busy
  credit needs, where it syncs).
- ``serve.decode``: the decode loop through its final sync.
- ``serve.finish``: the token concat, registry save, lifecycle sweep and
  the session's stats at the end of a request.
- ``tuner.register``: ``TuningCoordinator.register``; for a new tuner this
  holds the reference measurement its budget charges at start.
- ``tuner.pump``: one ``TuningCoordinator.pump`` (a ``maybe_pump`` that
  does not pump opens none).
- ``tuner.evaluate``: ``Evaluator.evaluate`` of a variant or reference.
- ``tuner.wait_inputs``: inside ``tuner.evaluate``, the wait for its
  inputs before the first call: serving work still queued on the device
  (with ``fresh_args``, and the first call's copy of it).
- ``tuner.gate``: the oracle gate's check of a measured variant.
- ``tuner.generate``: ``Compilette.generate`` on a compile-farm worker or
  in a synchronous wake.

Counters:

- ``compile.<span>``: backend compiles, loads from the persistent compile
  cache included (JAX's backend-compile duration event), with seconds, on
  a thread whose innermost open span is ``<span>``; ``compile.(none)``
  where that thread has no span open.
- ``compile.request``: the same, for a thread inside ``serve.request``.
- ``moe.rows``: a MoE model's decode steps, the (token, expert) rows
  routed to the experts this device holds, summed over layers; counted
  on the device and added once per request in ``serve.finish``.
- ``moe.active``: the same steps, the held experts hit at least once,
  summed over layers: the expert weight reads the steps needed.
- ``attn.prefill_tiles``: a served prefill's fused attention kernel, the
  tiles of its grid summed over (layer, sequence, KV head); computed on
  the host from the static shapes after the prefill
  (``kernels.attention.ops.prefill_kernel_tiles``); zero where the
  chunked jnp path served (off a TPU, under a window).
- ``attn.prefill_tiles_run``: the same prefills, the tiles the kernel
  computed: those that meet the causal lower triangle. The rest were
  neither fetched nor computed.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Callable

import jax

__all__ = ["counter", "snapshot", "span", "traced"]

# JAX's backend-compile duration event; it also fires on a load from the
# persistent compilation cache (both go through compile_or_get_cached)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REQUEST = "serve.request"

_lock = threading.Lock()
_table: dict[str, list[float]] = {}      # name -> [seconds, count]
_local = threading.local()


def _stack() -> list[str]:
    """The calling thread's open spans, innermost last."""
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _add(name: str, seconds: float, n: int) -> None:
    with _lock:
        entry = _table.get(name)
        if entry is None:
            _table[name] = [seconds, n]
        else:
            entry[0] += seconds
            entry[1] += n


class span:
    """Context manager: time one region under ``name`` (module docstring)."""

    __slots__ = ("name", "_t0", "_trace")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        _stack().append(self.name)
        self._trace = jax.profiler.TraceAnnotation(self.name)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        _stack().pop()
        _add(self.name, dt, 1)


def traced(name: str) -> Callable[[Callable[..., Any]], Callable[..., Any]]:
    """Decorator: each call of the function is one span ``name``."""

    def wrap(fn: Callable[..., Any]) -> Callable[..., Any]:
        @functools.wraps(fn)
        def inner(*args: Any, **kwargs: Any) -> Any:
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def counter(name: str, n: int = 1) -> None:
    """Add ``n`` to the count of ``name``."""
    _add(name, 0.0, n)


def snapshot() -> dict[str, dict[str, float]]:
    """A copy of the table: ``{name: {"s": seconds, "n": count}}``."""
    with _lock:
        return {k: {"s": s, "n": n} for k, (s, n) in _table.items()}


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    _add("compile." + (stack[-1] if stack else "(none)"), duration, 1)
    if REQUEST in stack:
        _add("compile.request", duration, 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
