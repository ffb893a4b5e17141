"""RMSNorm kernel: wrapper + compilette + cost model (memory-bound op)."""

from __future__ import annotations

import math
from typing import Any

import jax

import jax.numpy as jnp

from repro.core.compilette import Compilette
from repro.core.profiles import TPU_V5E, DeviceProfile
from repro.core.tuning_space import Param, Point, TuningSpace
from repro.kernels.catalog import KernelDef, example_fill
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas

DEFAULT_POINT: Point = {"block_rows": 128, "lookahead": 1}


def make_space(N: int, d: int, *, vmem_kb: int = TPU_V5E.vmem_kb) -> TuningSpace:
    params = (
        Param("block_rows", (8, 32, 128, 512), phase=1, switch_rank=0),
        Param("lookahead", (0, 1, 2), phase=2),
    )

    def validator(p: Point) -> bool:
        rows = min(p["block_rows"], N)
        return 2 * rows * d * 4 <= vmem_kb * 1024

    def no_leftover(p: Point) -> float:
        rows = min(p["block_rows"], N)
        n = math.ceil(N / rows)
        return (n * rows) / N - 1.0

    return TuningSpace(params=params, validator=validator,
                       no_leftover=no_leftover)


def rmsnorm_cost_model(point: Point, spec: dict[str, Any],
                       profile: DeviceProfile) -> float:
    N, d = spec["N"], spec["d"]
    rows = min(point["block_rows"], N)
    if 2 * rows * d * 4 > profile.vmem_kb * 1024:
        return float("inf")
    flops = 4.0 * N * d
    compute_s = flops / (profile.vpu_gflops * 1e9)
    mem_s = 2.0 * N * d * 4.0 / (profile.hbm_gbps * 1e9)
    steps = math.ceil(N / rows)
    overhead_s = steps * profile.grid_step_overhead_ns * 1e-9
    t = profile.exec_time_s(compute_s, mem_s, overhead_s)
    if not profile.overlap and point["lookahead"] > 0:
        t -= min(compute_s, mem_s) * min(0.35 * point["lookahead"], 0.7)
    return t


def make_rmsnorm_compilette(N: int, d: int, *,
                            vmem_kb: int = TPU_V5E.vmem_kb) -> Compilette:
    space = make_space(N, d, vmem_kb=vmem_kb)

    def generate(point: Point, **spec: Any):
        @jax.jit
        def fn(x, w):
            return rmsnorm_pallas(x, w, point)
        return fn

    def cost_model(point, spec, profile):
        full = {"N": N, "d": d}
        full.update(spec)
        return rmsnorm_cost_model(point, full, profile)

    return Compilette("rmsnorm", space, generate, cost_model=cost_model)


# ---------------------------------------------------------- kernel catalog
def _catalog_generate(point: Point, spec: dict[str, Any], *,
                      interpret: bool | None = None):
    @jax.jit
    def fn(x, w):
        return rmsnorm_pallas(x, w, point, interpret=interpret)
    return fn


def _extract_spec(x, w, **overrides: Any) -> dict[str, Any]:
    N, d = x.shape
    return {"N": int(N), "d": int(d), "dtype": str(x.dtype), **overrides}


def _abstract_args(spec: dict[str, Any]) -> tuple:
    dt = spec.get("dtype", "float32")
    return (jax.ShapeDtypeStruct((spec["N"], spec["d"]), dt),
            jax.ShapeDtypeStruct((spec["d"],), dt))


def _example_args(spec: dict[str, Any]) -> tuple:
    dt = spec.get("dtype", "float32")
    return (example_fill((spec["N"], spec["d"]), dt),
            example_fill((spec["d"],), dt))


KERNEL = KernelDef(
    name="rmsnorm",
    make_space=lambda spec: make_space(spec["N"], spec["d"]),
    generate=_catalog_generate,
    cost_model=rmsnorm_cost_model,
    extract_spec=_extract_spec,
    abstract_args=_abstract_args,
    example_args=_example_args,
    default_point=DEFAULT_POINT,
    oracle=rmsnorm_ref,
    tolerance={"rtol": 1e-3, "atol": 1e-5},
)


__all__ = ["DEFAULT_POINT", "KERNEL", "make_space", "make_rmsnorm_compilette",
           "rmsnorm_cost_model", "rmsnorm_pallas", "rmsnorm_ref"]
