"""Matmul kernel: jit wrapper, compilette factory, analytical cost model.

This is the framework's hot-spot kernel. The online auto-tuner owns the
choice of tuning point per (shape × device); model code calls
``tuned_matmul`` which consults the tuned registry.
"""

from __future__ import annotations

import math
from typing import Any

import jax.numpy as jnp

from repro.core.compilette import Compilette
from repro.core.profiles import TPU_V5E, DeviceProfile
from repro.core.tuning_space import Param, Point, TuningSpace
from repro.kernels.catalog import KernelDef, example_fill
from repro.kernels.matmul.matmul import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref

DEFAULT_POINT: Point = {
    "block_m": 128, "block_n": 128, "block_k": 256,
    "unroll": 1, "order": "mn", "scratch": 1, "lookahead": 1,
}


def make_space(
    M: int, N: int, K: int,
    *,
    dtype_bytes: int = 4,
    vmem_kb: int = TPU_V5E.vmem_kb,
) -> TuningSpace:
    # block_k options past K are all holes (validator: block_k > K), so a
    # small-K problem would otherwise have an EMPTY space; keep the pow2
    # options that fit and fall back to the exact extent when none do.
    bk_options = tuple(v for v in (128, 256, 512) if v <= K) or (int(K),)
    params = (
        # phase 1 — structural (analogues: coldUF, vectLen, chunking, hotUF)
        Param("block_m", (64, 128, 256, 512), phase=1, switch_rank=0),
        Param("block_n", (128, 256, 512), phase=1, switch_rank=1),
        Param("block_k", bk_options, phase=1, switch_rank=2),
        Param("unroll", (1, 2, 4), phase=1, switch_rank=3),
        # phase 2 — codegen options (IS, SM, pldStride analogues)
        Param("order", ("mn", "nm"), phase=2),
        Param("scratch", (1, 0), phase=2),
        Param("lookahead", (0, 1, 2), phase=2),
    )

    def validator(p: Point) -> bool:
        if p["block_k"] % p["unroll"] != 0:
            return False
        if p["block_m"] > M + 8 or p["block_n"] > N + 128 or p["block_k"] > K:
            return False  # degenerate over-tiling
        # VMEM footprint hole (the register-pressure analogue)
        words = (
            p["block_m"] * p["block_k"]
            + p["block_k"] * p["block_n"]
            + p["block_m"] * p["block_n"] * (2 if p["scratch"] else 1)
        )
        return words * dtype_bytes <= vmem_kb * 1024

    def no_leftover(p: Point) -> float:
        # fraction of padded (wasted) grid cells; 0 = leftover-free
        waste = 1.0
        for dim, blk in ((M, p["block_m"]), (N, p["block_n"]), (K, p["block_k"])):
            n = math.ceil(dim / blk)
            waste *= (n * blk) / dim
        return waste - 1.0

    return TuningSpace(params=params, validator=validator, no_leftover=no_leftover)


# --------------------------------------------------------------------- cost
def matmul_cost_model(
    point: Point, spec: dict[str, Any], profile: DeviceProfile
) -> float:
    """Analytical execution-time estimate of a matmul variant (seconds)."""
    M, N, K = spec["M"], spec["N"], spec["K"]
    b = spec.get("dtype_bytes", 4)
    bm, bn, bk = point["block_m"], point["block_n"], point["block_k"]
    unroll, order = point["unroll"], point["order"]
    scratch, lookahead = point["scratch"], point["lookahead"]

    words = bm * bk + bk * bn + bm * bn * (2 if scratch else 1)
    if words * b > profile.vmem_kb * 1024:
        return float("inf")  # late-discovered hole on this device

    n_m, n_n, n_k = math.ceil(M / bm), math.ceil(N / bn), math.ceil(K / bk)
    flops = 2.0 * (n_m * bm) * (n_n * bn) * (n_k * bk)  # padded work counts

    # MXU pipeline efficiency: unrolling supplies independent chains (hotUF);
    # fat (OOO-analogue) cores extract them in hardware.
    if profile.overlap:
        eff_u = max(0.88, unroll / (unroll + 0.35))
    else:
        eff_u = unroll / (unroll + 1.2)
    eff_k = bk / (bk + 64.0)  # per-step MXU drain
    compute_s = flops / (profile.peak_flops * eff_u * eff_k)

    bytes_a = M * K * n_n * b
    bytes_b = K * N * n_m * b
    bytes_c = M * N * (2 * n_k - 1 if not scratch else 1) * b
    mem_s = (bytes_a + bytes_b + bytes_c) / (profile.hbm_gbps * 1e9)

    steps = n_m * n_n * n_k
    # order (IS analogue): the right traversal keeps the streamed operand
    # contiguous; wrong choice pays extra per-step latency.
    good_order = (order == "nm") == (M >= N)
    step_ns = profile.grid_step_overhead_ns * (0.8 if good_order else 1.0)
    overhead_s = steps * step_ns * 1e-9

    t = profile.exec_time_s(compute_s, mem_s, overhead_s)
    if not profile.overlap and lookahead > 0:
        # pldStride analogue: deeper DMA lookahead recovers part of the
        # serialization on lean cores.
        t -= min(compute_s, mem_s) * min(0.35 * lookahead, 0.7)
    return t


def matmul_flops_bytes(spec: dict[str, Any], point: Point) -> tuple[float, float]:
    M, N, K = spec["M"], spec["N"], spec["K"]
    b = spec.get("dtype_bytes", 4)
    bm, bn = point["block_m"], point["block_n"]
    n_m, n_n = math.ceil(M / bm), math.ceil(N / bn)
    return 2.0 * M * N * K, float((M * K * n_n + K * N * n_m + M * N) * b)


# --------------------------------------------------------------- compilette
def make_matmul_compilette(
    M: int, N: int, K: int,
    *,
    dtype=jnp.float32,
    vmem_kb: int = TPU_V5E.vmem_kb,
) -> Compilette:
    import jax

    space = make_space(M, N, K, dtype_bytes=jnp.dtype(dtype).itemsize, vmem_kb=vmem_kb)

    def generate(point: Point, **spec: Any):
        @jax.jit
        def fn(a, b):
            return matmul_pallas(a, b, point, out_dtype=jnp.float32)
        return fn

    def cost_model(point: Point, spec: dict[str, Any], profile: DeviceProfile) -> float:
        full = {"M": M, "N": N, "K": K, "dtype_bytes": jnp.dtype(dtype).itemsize}
        full.update(spec)
        return matmul_cost_model(point, full, profile)

    return Compilette("matmul", space, generate, cost_model=cost_model)


def tuned_matmul(a, b, *, point: Point | None = None):
    """Public wrapper: run the kernel with a tuned (or default) point."""
    point = dict(DEFAULT_POINT if point is None else point)
    return matmul_pallas(a, b, point, out_dtype=jnp.float32)


# ---------------------------------------------------------- kernel catalog
def _catalog_space(spec: dict[str, Any]) -> TuningSpace:
    return make_space(
        spec["M"], spec["N"], spec["K"],
        dtype_bytes=jnp.dtype(spec.get("dtype", "float32")).itemsize)


def _catalog_generate(point: Point, spec: dict[str, Any], *,
                      interpret: bool | None = None):
    import jax

    @jax.jit
    def fn(a, b):
        return matmul_pallas(a, b, point, out_dtype=jnp.float32,
                             interpret=interpret)
    return fn


def _catalog_cost(point: Point, spec: dict[str, Any], profile) -> float:
    full = {"dtype_bytes": jnp.dtype(spec.get("dtype", "float32")).itemsize}
    full.update(spec)
    return matmul_cost_model(point, full, profile)


def _extract_spec(a, b, **overrides: Any) -> dict[str, Any]:
    M, K = a.shape
    _, N = b.shape
    return {"M": int(M), "N": int(N), "K": int(K),
            "dtype": str(a.dtype), **overrides}


def _shapes(spec: dict[str, Any]):
    dt = spec.get("dtype", "float32")
    return ((spec["M"], spec["K"]), dt), ((spec["K"], spec["N"]), dt)


def _abstract_args(spec: dict[str, Any]) -> tuple:
    import jax

    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in _shapes(spec))


def _example_args(spec: dict[str, Any]) -> tuple:
    return tuple(example_fill(s, d) for s, d in _shapes(spec))


KERNEL = KernelDef(
    name="matmul",
    make_space=_catalog_space,
    generate=_catalog_generate,
    cost_model=_catalog_cost,
    extract_spec=_extract_spec,
    abstract_args=_abstract_args,
    example_args=_example_args,
    default_point=DEFAULT_POINT,
    oracle=matmul_ref,
    # tiled f32 accumulation vs one fused dot: order-of-summation only
    tolerance={"rtol": 1e-3, "atol": 1e-5},
)


__all__ = [
    "DEFAULT_POINT",
    "KERNEL",
    "make_space",
    "make_matmul_compilette",
    "matmul_cost_model",
    "matmul_flops_bytes",
    "tuned_matmul",
    "matmul_ref",
]
