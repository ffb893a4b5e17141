"""Seconds of backend compiles and compile-cache loads on a thread
serving a request, over the window: the program's ``compile.request``
counter (``chipbench/spans.py``)."""

from chipbench import spans


def read(run):
    d = spans.delta(run, "compile.request")
    return None if d is None else d["s"]
