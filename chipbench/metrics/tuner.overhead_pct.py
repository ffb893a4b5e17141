"""The tuner's own charge over the window (Δ``tuning_spent_s`` of the
session's stats: generation and evaluation of variants) as a share of
the window."""


def read(run):
    if run.tuning_before is None:
        return None
    spent = (run.tuning_after["tuning_spent_s"]
             - run.tuning_before["tuning_spent_s"])
    return 100.0 * spent / run.window_s
