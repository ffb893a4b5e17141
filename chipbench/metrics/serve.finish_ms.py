"""Milliseconds per request in ``generate``'s finish (token concat,
registry save, lifecycle sweep, the session's stats): the program's
``serve.finish`` span over the window (``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    return spans.per_request_ms(run, "serve.finish")
