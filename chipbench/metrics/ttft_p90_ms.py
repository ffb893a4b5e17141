"""90th percentile over the window's requests of the time from the call
into the serve entry to the moment the prefill step's logits, so the
first token, are ready on the device (harness clock)."""

from chipbench.harness import percentile


def read(run):
    if not run.requests:
        return None
    return 1e3 * percentile(
        [r["t_first"] - r["t_start"] for r in run.requests], 90)
