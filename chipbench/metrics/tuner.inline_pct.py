"""Tuning work on the serving thread over the window, as a share of the
window: the program's ``tuner.pump`` and ``tuner.register`` spans, less
the ``tuner.wait_inputs`` inside them, which is serving work already
queued on the device that an evaluation waits out before its first call
(``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    parts = [spans.delta(run, name)
             for name in ("tuner.pump", "tuner.register", "tuner.wait_inputs")]
    if None in parts or run.window_s <= 0:
        return None
    pump, register, wait = (p["s"] for p in parts)
    return 100.0 * (pump + register - wait) / run.window_s
