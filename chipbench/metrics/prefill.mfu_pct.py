"""Share of the chip's peak FLOP/s in prefill: the FLOPs every request's
prefill needs (the family's count from shapes) over the sum of the
``prefill_s`` spans ``generate`` reports (prefill and cache widening)."""


def read(run):
    if not run.requests:
        return None
    fam, cfg = run.cell.family, run.cell.config
    flops = sum(fam.prefill_flops(cfg, r["batch"], r["prompt_len"])
                for r in run.requests)
    seconds = sum(r["prefill_s"] for r in run.requests)
    return 100.0 * flops / seconds / run.peak["flops_per_s"]
