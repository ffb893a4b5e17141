"""Share of the roofline in the decode steps: each step's least time on
the chip (the larger of its FLOPs over peak FLOP/s and its bytes over
peak bandwidth; bytes are every weight once, the embedding rows looked
up, the K/V cache up to each position and the position written) summed
over the window, over the sum of the ``decode_s`` spans."""

from chipbench.work import decode_bound_s


def read(run):
    if not run.requests:
        return None
    fam, cfg = run.cell.family, run.cell.config
    least = sum(decode_bound_s(fam, cfg, run.peak, r["batch"],
                               r["prompt_len"], r["new_tokens"])[0]
                for r in run.requests)
    return 100.0 * least / sum(r["decode_s"] for r in run.requests)
