"""90th percentile over the window's requests of the time per output
token after the first: from the first token being ready to the request's
return, over new_tokens - 1 (harness clock)."""

from chipbench.harness import percentile


def read(run):
    if not run.requests:
        return None
    return 1e3 * percentile(
        [(r["t_end"] - r["t_first"]) / (r["new_tokens"] - 1)
         for r in run.requests], 90)
