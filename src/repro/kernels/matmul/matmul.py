"""Tiled matmul Pallas TPU kernel with an auto-tunable variant space.

Tuning-point fields (TPU analogues of the paper's deGoal parameters):

  block_m   — rows per program instance        (coldUF: grid coarsening)
  block_n   — lanes per program instance       (vectLen: vector length)
  block_k   — reduction chunk per grid step
  unroll    — independent sub-accumulators within block_k (hotUF: unrolling
              with distinct registers to hide MXU latency)
  order     — "mn" | "nm" grid traversal       (IS: scheduling analogue)
  scratch   — 1: accumulate in a VMEM scratch buffer, publish once
              0: accumulate straight into the output block ("stack
              minimization": fewer live buffers)
  lookahead — DMA pipeline-depth hint (pldStride analogue). Functionally
              inert here (Mosaic double-buffers automatically); consumed by
              the analytical cost model and, on real hardware, by
              emit_pipeline depth.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

Point = dict[str, Any]


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, unroll: int, n_k: int,
               k_rem: int):
    k = pl.program_id(2)
    acc = acc_ref if acc_ref is not None else o_ref

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[...]
    b = b_ref[...]
    bk = a.shape[-1]
    if k_rem:
        # Leftover handling (deGoal "leftover code" analogue): the final
        # partial K block is masked so padding cannot poison the reduction.
        valid = jnp.where(k == n_k - 1, k_rem, bk)
        kcol = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        a = jnp.where(kcol < valid, a, 0)
        krow = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
        b = jnp.where(krow < valid, b, 0)
    # hotUF: split the K chunk into `unroll` independent accumulators so the
    # MXU pipeline sees independent chains; summed pairwise at the end.
    sub = bk // unroll
    partials = []
    for u in range(unroll):
        au = a[:, u * sub:(u + 1) * sub]
        bu = b[u * sub:(u + 1) * sub, :]
        partials.append(
            jnp.dot(au, bu, preferred_element_type=jnp.float32)
        )
    total = functools.reduce(jnp.add, partials)
    acc[...] += total.astype(acc.dtype)

    if acc_ref is not None:
        @pl.when(k == n_k - 1)
        def _publish():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    point: Point,
    *,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] with the variant described by ``point``."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = point["block_m"], point["block_n"], point["block_k"]
    unroll = point.get("unroll", 1)
    order = point.get("order", "mn")
    use_scratch = bool(point.get("scratch", 1))

    n_m, n_n, n_k = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    if order == "mn":
        grid = (n_m, n_n, n_k)
        a_map = lambda i, j, k: (i, k)
        b_map = lambda i, j, k: (k, j)
        o_map = lambda i, j, k: (i, j)
    else:  # "nm": swap traversal of the parallel dims
        grid = (n_n, n_m, n_k)
        a_map = lambda j, i, k: (i, k)
        b_map = lambda j, i, k: (k, j)
        o_map = lambda j, i, k: (i, j)

    if not use_scratch and out_dtype != jnp.float32:
        raise ValueError("scratch=0 requires fp32 output (in-place accumulation)")

    kernel = functools.partial(
        _mm_kernel if use_scratch else _mm_kernel_noscratch,
        unroll=unroll,
        n_k=n_k,
        k_rem=K % bk,
    )
    scratch_shapes = [pltpu.VMEM((bm, bn), jnp.float32)] if use_scratch else []

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(interpret),
    )(a, b)


def _mm_kernel_noscratch(a_ref, b_ref, o_ref, *, unroll: int, n_k: int,
                         k_rem: int):
    _mm_kernel(a_ref, b_ref, o_ref, None, unroll=unroll, n_k=n_k, k_rem=k_rem)
