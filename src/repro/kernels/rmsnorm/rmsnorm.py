"""Fused RMSNorm Pallas TPU kernel (row-tiled, fp32 statistics).

Tuning point: block_rows (coldUF analogue — rows per program instance),
lookahead (pld analogue, cost-model only). The feature dim stays whole per
program (the reduction axis must be resident).
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


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)           # (rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,          # (N, d) — callers flatten (B, T, d)
    w: jax.Array,          # (d,)
    point: Point,
    *,
    eps: float = 1e-6,
    interpret: bool | None = None,
) -> jax.Array:
    N, d = x.shape
    rows = min(point.get("block_rows", 128), N)
    grid = (pl.cdiv(N, rows),)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=pallas_interpret(interpret),
    )(x, w.reshape(1, d))
