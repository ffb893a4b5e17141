"""VIPS ``im_lintra_vec`` Pallas TPU kernel (memory-bound case study).

``y[h, w, band] = a[band] * x[h, w, band] + b[band]`` — each pixel is
loaded and processed exactly once, so the kernel is HBM-bandwidth-bound.
Run-time constants specialized into the generated code: the number of
bands and the image width (as in the paper's compilette).

The image is laid out as (H, W·bands): the band dimension is folded into
the minor axis so the per-band multiply/add becomes a tiled broadcast.

Tuning point: block_h (coldUF), block_w (vectLen, lane-multiples), unroll
(hotUF: independent row strips), order/scratch/lookahead (phase 2).
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


def _lintra_kernel(x_ref, ab_ref, o_ref, *, unroll: int):
    x = x_ref[...]                      # (bh, bw)
    a = ab_ref[0:1, :]                  # (1, bw) multiplication factors
    b = ab_ref[1:2, :]                  # (1, bw) addition factors
    bh = x.shape[0]
    sub = bh // unroll
    # hotUF: independent row strips keep multiple FMA chains in flight.
    outs = []
    for u in range(unroll):
        xs = x[u * sub:(u + 1) * sub, :]
        outs.append(xs * a + b)
    o_ref[...] = jnp.concatenate(outs, axis=0) if unroll > 1 else outs[0]


def lintra_pallas(
    x: jax.Array,        # (H, W*bands)
    ab: jax.Array,       # (2, W*bands): row 0 = a tiled, row 1 = b tiled
    point: Point,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    H, WB = x.shape
    bh, bw = point["block_h"], point["block_w"]
    bw = min(bw, WB)
    unroll = point.get("unroll", 1)

    n_h, n_w = pl.cdiv(H, bh), pl.cdiv(WB, bw)
    order = point.get("order", "hw")
    if order == "hw":
        grid = (n_h, n_w)
        x_map = lambda i, j: (i, j)
        ab_map = lambda i, j: (0, j)
    else:
        grid = (n_w, n_h)
        x_map = lambda j, i: (i, j)
        ab_map = lambda j, i: (0, j)

    kernel = functools.partial(_lintra_kernel, unroll=unroll)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, bw), x_map),
            pl.BlockSpec((2, bw), ab_map),
        ],
        out_specs=pl.BlockSpec((bh, bw), x_map),
        out_shape=jax.ShapeDtypeStruct((H, WB), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=pallas_interpret(interpret),
    )(x, ab)
