"""Host time per request outside the program's prefill and decode spans:
the harness-clock time of the request minus the ``prefill_s`` and
``decode_s`` that ``generate`` reports, averaged over the window."""


def read(run):
    if not run.requests:
        return None
    host = [r["t_end"] - r["t_start"] - r["prefill_s"] - r["decode_s"]
            for r in run.requests]
    return 1e3 * sum(host) / len(host)
