"""Flash-attention Pallas TPU kernel (blockwise online softmax), GQA-aware.

Tuning point:
  block_q   — query rows per program (coldUF analogue)
  block_kv  — key/value rows per inner grid step (vectLen analogue)
  sched     — "arbitrary" | "parallel" semantics hint on the kv axis (IS)
  lookahead — DMA pipeline depth hint (pld analogue, cost-model only)

Layout: q (B*H, Tq, Dh), k/v (B*Hk, Tkv, Dh) with H = G·Hk. The kv block
index map folds the GQA group: kv head = q head // G.
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
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, block_q: int, block_kv: int,
               n_kv: int, q_offset: int, t_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (bq, d)
    k = k_ref[0]                       # (bkv, d)
    v = v_ref[0]
    ragged = t_kv % block_kv != 0
    if ragged:
        # leftover handling: zero the padded tail of the final kv block
        kv_idx = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, k.shape, 0)
        k = jnp.where(kv_idx < t_kv, k, 0)
        v = jnp.where(kv_idx < t_kv, v, 0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (bq, bkv)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if ragged:
        s = jnp.where(k_pos < t_kv, s, NEG_INF)

    m_prev = m_ref[...]                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _publish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,      # (B, Tq, H, Dh)
    k: jax.Array,      # (B, Tkv, Hk, Dh)
    v: jax.Array,      # (B, Tkv, Hk, Dh)
    point: Point,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    B, Tq, H, Dh = q.shape
    _, Tkv, Hk, _ = k.shape
    G = H // Hk
    scale = float(scale if scale is not None else Dh ** -0.5)
    bq = min(point["block_q"], Tq)
    bkv = min(point["block_kv"], Tkv)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hk, Tkv, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hk, Tkv, Dh)

    n_q, n_kv = pl.cdiv(Tq, bq), pl.cdiv(Tkv, bkv)
    grid = (B * H, n_q, n_kv)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv,
        n_kv=n_kv, q_offset=q_offset, t_kv=Tkv,
    )
    sem = point.get("sched", "arbitrary")
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda bh, iq, ik, g=G: (bh // g, ik, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda bh, iq, ik, g=G: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", sem)
        ),
        interpret=pallas_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, Tq, Dh).transpose(0, 2, 1, 3)
