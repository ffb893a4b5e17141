"""Device peaks and the least time of a request's decode steps.

The peaks come from ``peaks.json``, keyed by ``device_kind``, each with
its source. The operations and bytes of a step are the configuration's
family module's (``chipbench/reference/<family>.py``): they count the
work the algorithm requires, not what a program happens to do.
"""

from __future__ import annotations

import json
import os
from typing import Any

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def decode_bound_s(fam: Any, cfg: dict[str, Any], pk: dict[str, Any],
                   batch: int, prompt: int,
                   new_tokens: int) -> tuple[float, int]:
    """Least time of a request's decode steps on a chip of peaks ``pk``,
    and the FLOPs they need. Step i (1 .. new_tokens - 1) writes position
    prompt + i - 1 and reads prompt + i positions."""
    least, flops = 0.0, 0
    for i in range(1, new_tokens):
        f, byt = fam.decode_step(cfg, batch, prompt + i)
        least += max(f / pk["flops_per_s"], byt / pk["hbm_bytes_per_s"])
        flops += f
    return least, flops
