"""The program's span and counter table over the window.

``TuningSession.stats()["telemetry"]`` holds, per span or counter name,
its seconds (``s``) and count (``n``) since the process started
(``repro.core.telemetry``). The harness keeps the session's stats from
before and after the window, so a metric reads their difference. A
program without the table gives None.
"""

from __future__ import annotations

from typing import Any


def delta(run: Any, name: str) -> dict[str, float] | None:
    """``{"s": ..., "n": ...}`` of ``name`` over the window, zero where
    the name never occurred; None where the program keeps no table."""
    tables = [(stats or {}).get("telemetry")
              for stats in (run.tuning_before, run.tuning_after)]
    if any(t is None for t in tables):
        return None
    zero = {"s": 0.0, "n": 0}
    before, after = (t.get(name, zero) for t in tables)
    return {k: after[k] - before[k] for k in ("s", "n")}


def per_request_ms(run: Any, name: str) -> float | None:
    """Milliseconds of span ``name`` per served request over the window."""
    span, requests = delta(run, name), delta(run, "serve.request")
    if span is None or not requests["n"]:
        return None
    return 1e3 * span["s"] / requests["n"]
