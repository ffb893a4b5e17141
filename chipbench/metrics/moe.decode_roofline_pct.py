"""Share of the roofline in the decode steps of a MoE model, at the
routing actually served: the window's least decode time on the chip
(the larger of its FLOPs over peak FLOP/s and its bytes over peak
bandwidth) over the sum of the ``decode_s`` spans. Expert FLOPs and
bytes come from the program's routing counters (Δ``moe.rows`` rows of
expert work, Δ``moe.active`` expert weight reads); the rest of each
step (attention, router, head, cache) from the family's counts. None
where the program keeps no such counters."""

from chipbench import spans


def read(run):
    rows, active = spans.delta(run, "moe.rows"), spans.delta(run, "moe.active")
    if rows is None or not active["n"] or not run.requests:
        return None
    fam, cfg = run.cell.family, run.cell.config
    flops = 2 * rows["n"] * fam.expert_params(cfg)
    byt = active["n"] * fam.expert_params(cfg) * fam.dtype_bytes(cfg)
    for r in run.requests:
        for i in range(1, r["new_tokens"]):
            f, b = fam.decode_step(cfg, r["batch"], r["prompt_len"] + i,
                                   rows=0, reads=0)
            flops += f
            byt += b
    least = max(flops / run.peak["flops_per_s"],
                byt / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(r["decode_s"] for r in run.requests)
