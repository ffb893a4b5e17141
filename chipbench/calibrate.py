"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --seconds <s> --control int8,fp8 --control-seeds 6 --out <file.json>

For each seed, one run of the cell as ``run.py`` makes it (set-up, a
window of ``--seconds`` at the cell's own load, the reference check) and
the numbers its check compares: the lower readings. For the first
``--control-seeds`` seeds, each control on the same sampled requests:
the reference computed at the lower precision in the program's place,
and the same numbers for the tokens it puts first: the upper readings.
Each control is judged by the cell's own limits, as a run's check is
(``control_correct.<kind>``, which has to read false). All in one
process, so the program's compiles are paid once. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="int8,fp8",
                    help="comma-separated lower precisions")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import check, harness, weights, work
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    peak = work.peak(dev.device_kind)
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, t_proc0=t,
                          peak=peak, keep_sample=True)
        row = {"seed": seed, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               "numbers": check.numbers(out["gaps"]),
               "check": out["check"],
               "metrics": out["metrics"],
               "memory_peak_bytes": out["memory_peak_bytes"]}
        gc.collect()    # the run's weights go before the next are drawn
        if n < args.control_seeds:
            t = time.perf_counter()
            params = weights.draw(cell.family.param_specs(cell.config), seed,
                                  cell.adapter.program_config(
                                      cell.config).param_dtype)
            for kind in args.control.split(","):
                got = check.numbers(check.gaps(
                    cell.family, params, cell.config, out["sample"],
                    precision=kind))
                held, _ = check.judge(got, cell.check["limits"])
                row[f"control.{kind}"] = got
                row[f"control_correct.{kind}"] = held
            row["control_s"] = time.perf_counter() - t
            del params
        rows.append(row)
        print(json.dumps(row), flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "control": args.control,
                       "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
