"""Squared euclidean distance Pallas TPU kernel (Streamcluster case study).

``dist[n, m] = sum_d (X[n, d] - C[m, d])**2`` — the paper's CPU-bound
kernel. The *dimension* ``d`` is a run-time constant specialized into the
generated code (deGoal ``#()`` analogue = JAX trace-time constant).

Tuning point:
  block_n   — points per program        (coldUF analogue)
  block_m   — centers per program
  block_d   — d-chunk per grid step     (vectLen × 128 lanes)
  unroll    — independent accumulators inside block_d (hotUF)
  vectorize — 1: MXU path (‖x‖² + ‖c‖² − 2·x@cᵀ)   (VE=SIMD)
              0: VPU path (broadcast-diff-square-sum)  (VE=SISD)
  order, scratch, lookahead — phase-2 codegen options (IS/SM/pld analogues)
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


def _euclid_kernel(x_ref, c_ref, o_ref, acc_ref, *, unroll: int, n_d: int,
                   vectorize: bool, d_rem: int):
    kd = pl.program_id(2)
    acc = acc_ref if acc_ref is not None else o_ref

    @pl.when(kd == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]  # (bn, bd)
    c = c_ref[...]  # (bm, bd)
    bd = x.shape[-1]
    if d_rem:
        # leftover code: mask the final partial d chunk
        valid = jnp.where(kd == n_d - 1, d_rem, bd)
        xi = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(xi < valid, x, 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        c = jnp.where(ci < valid, c, 0)
    sub = bd // unroll
    partials = []
    for u in range(unroll):
        xs = x[:, u * sub:(u + 1) * sub]
        cs = c[:, u * sub:(u + 1) * sub]
        if vectorize:
            # MXU path: ||x-c||^2 = ||x||^2 + ||c||^2 - 2 x.c
            xx = jnp.sum(xs * xs, axis=-1, keepdims=True)        # (bn,1)
            cc = jnp.sum(cs * cs, axis=-1, keepdims=True).T      # (1,bm)
            xc = jnp.dot(xs, cs.T, preferred_element_type=jnp.float32)
            partials.append(xx + cc - 2.0 * xc)
        else:
            diff = xs[:, None, :] - cs[None, :, :]               # (bn,bm,sub)
            partials.append(jnp.sum(diff * diff, axis=-1))
    total = functools.reduce(jnp.add, partials)
    acc[...] += total.astype(acc.dtype)

    if acc_ref is not None:
        @pl.when(kd == n_d - 1)
        def _publish():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _euclid_kernel_noscratch(x_ref, c_ref, o_ref, *, unroll, n_d, vectorize,
                             d_rem):
    _euclid_kernel(x_ref, c_ref, o_ref, None, unroll=unroll, n_d=n_d,
                   vectorize=vectorize, d_rem=d_rem)


def euclid_pallas(
    x: jax.Array,       # (N, D) points
    c: jax.Array,       # (M, D) centers
    point: Point,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    N, D = x.shape
    M, D2 = c.shape
    assert D == D2
    bn, bm, bd = point["block_n"], point["block_m"], point["block_d"]
    bd = min(bd, D)
    unroll = point.get("unroll", 1)
    use_scratch = bool(point.get("scratch", 1))
    order = point.get("order", "nm")
    vectorize = bool(point.get("vectorize", 1))

    n_n, n_m, n_d = pl.cdiv(N, bn), pl.cdiv(M, bm), pl.cdiv(D, bd)
    if order == "nm":
        grid = (n_n, n_m, n_d)
        x_map = lambda i, j, k: (i, k)
        c_map = lambda i, j, k: (j, k)
        o_map = lambda i, j, k: (i, j)
    else:
        grid = (n_m, n_n, n_d)
        x_map = lambda j, i, k: (i, k)
        c_map = lambda j, i, k: (j, k)
        o_map = lambda j, i, k: (i, j)

    kernel = functools.partial(
        _euclid_kernel if use_scratch else _euclid_kernel_noscratch,
        unroll=unroll, n_d=n_d, vectorize=vectorize, d_rem=D % bd,
    )
    scratch = [pltpu.VMEM((bn, bm), jnp.float32)] if use_scratch else []

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), x_map),
            pl.BlockSpec((bm, bd), c_map),
        ],
        out_specs=pl.BlockSpec((bn, bm), o_map),
        out_shape=jax.ShapeDtypeStruct((N, M), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(interpret),
    )(x, c)
