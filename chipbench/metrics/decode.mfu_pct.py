"""Share of the chip's peak FLOP/s in decoding: the FLOPs the window's
decode steps need over the sum of the ``decode_s`` spans."""

from chipbench.work import decode_bound_s


def read(run):
    if not run.requests:
        return None
    fam, cfg = run.cell.family, run.cell.config
    flops = sum(decode_bound_s(fam, cfg, run.peak, r["batch"],
                               r["prompt_len"], r["new_tokens"])[1]
                for r in run.requests)
    seconds = sum(r["decode_s"] for r in run.requests)
    return 100.0 * flops / seconds / run.peak["flops_per_s"]
