"""Variants the tuner generated and measured inside the window
(Δ``regenerations`` of the session's stats)."""


def read(run):
    if run.tuning_before is None:
        return None
    return (run.tuning_after["regenerations"]
            - run.tuning_before["regenerations"])
