"""Milliseconds per request in ``generate``'s set-up (model and jit
wrappers, the prefill and decode registrations): the program's
``serve.setup`` span over the window (``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    return spans.per_request_ms(run, "serve.setup")
