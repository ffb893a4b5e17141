"""Rows each expert weight read serves in the decode steps: the
(token, held expert) rows routed over the window over the held experts
hit, both summed over layers and steps (Δ``moe.rows`` / Δ``moe.active``,
the program's routing counters, ``chipbench/spans.py``). None where the
program keeps no such counters."""

from chipbench import spans


def read(run):
    rows, active = spans.delta(run, "moe.rows"), spans.delta(run, "moe.active")
    if rows is None or not active["n"]:
        return None
    return rows["n"] / active["n"]
