"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``. The run draws its
weights and prompts from ``--seed``, warms up every shape its traffic
uses (set-up), serves requests back to back for ``--seconds``, checks a
sample of what it served against the configuration's plain reference,
and prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
of a slice of the window), ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared beside its limit, which are
also the last lines on standard error.

It exits non-zero without a result line when JAX finds no TPU, or fewer
chips than the cell asks for. JAX's compile cache is kept in
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the cache lives in the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness, work

    cell = harness.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_proc0=T_PROC0, peak=work.peak(kind))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.pop("memory_peak_bytes")}
    summary = out.pop("trace", None)
    result = {k: out[k] for k in ("correct", "attempted", "failed", "metrics")}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        result["device"] = device
    result["check"] = out["check"]
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
