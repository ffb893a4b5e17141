"""Flash-attention Pallas TPU kernel (blockwise online softmax), GQA-folded.

Tuning point:
  block_q   — query rows per program (coldUF analogue)
  block_kv  — key/value rows per inner grid step (vectLen analogue)
  sched     — "arbitrary" | "parallel" semantics hint on the kv axis (IS)
  lookahead — DMA pipeline depth hint (pld analogue, cost-model only)

Grid (B, Hk, q blocks, kv blocks). One program holds the G = H // Hk
query heads that share a KV head (GQA fold): their q rows are stacked
once per q block into one (G·bq, Dh) operand, so each K/V tile is
fetched once per q block and meets all G heads in one matmul. Scores,
the running max and sum, and the output accumulator are float32 and
live only in VMEM; P is cast to V's dtype (bf16 as served) for P·V,
which accumulates in float32.

Causal skip: a tile that lies wholly above the diagonal is neither
fetched nor computed. Its body is not run (``pl.when``), and the k/v
index map clamps to the last tile the q block needs, so the pipeline
sees an unchanged block index and starts no DMA. The mask is applied only
on tiles that cross the diagonal or hold the ragged tail of the keys.
``causal_tiles`` counts the tiles of the grid and those computed.

Layout: q, k and v arrive as (B, T, H, Dh) and are transposed to
(B, H, T, Dh), so that a program's G heads are G (bq, Dh) blocks of one
q tile.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

Point = dict[str, Any]
NEG_INF = -1e30
# half of a v5e core's 128 MiB of VMEM: the stacked f32 scores of 512 x
# 1024 tiles of seven or eight heads need 28-31 MiB, past the compiler's
# default scoped limit of 16 MiB
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _last_kv_tile(iq, *, block_q: int, block_kv: int, t_q: int, n_kv: int,
                  q_offset: int, minimum: Callable = min):
    """The last kv tile that q block ``iq`` needs under the causal mask."""
    q_last = q_offset + minimum((iq + 1) * block_q, t_q) - 1
    return minimum(q_last // block_kv, n_kv - 1)


def causal_tiles(t_q: int, t_kv: int, block_q: int, block_kv: int,
                 q_offset: int = 0) -> tuple[int, int]:
    """(tiles in the grid, tiles computed) of one (batch, KV head) causal
    call: the computed tiles are those that meet the lower triangle."""
    bq, bkv = min(block_q, t_q), min(block_kv, t_kv)
    n_q, n_kv = pl.cdiv(t_q, bq), pl.cdiv(t_kv, bkv)
    run = sum(_last_kv_tile(iq, block_q=bq, block_kv=bkv, t_q=t_q,
                            n_kv=n_kv, q_offset=q_offset) + 1
              for iq in range(n_q))
    return n_q * n_kv, run


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, q_rows, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, block_q: int, block_kv: int,
               groups: int, n_kv: int, q_offset: int, t_q: int, t_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    rows = groups * block_q

    @pl.when(ik == 0)
    def _init():
        # the G heads' q rows stacked: one (G·bq, Dh) operand for every
        # kv tile of this q block
        for g in range(groups):
            q_rows[g * block_q:(g + 1) * block_q] = q_ref[g]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ragged = t_kv % block_kv != 0
    q_first = q_offset + iq * block_q
    k_first = ik * block_kv

    def step(masked: bool):
        k = k_ref[...]                 # (bkv, Dh), shared by the G heads
        v = v_ref[...]
        s = jax.lax.dot_general(
            q_rows[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (G·bq, bkv) f32, in VMEM only
        if masked:
            # row r is query q_first + r % bq of head r // bq
            q_pos = q_first + jax.lax.rem(jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_kv), 0), block_q)
            k_pos = k_first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_kv), 1)
            mask = k_pos < t_kv
            if causal:
                mask &= q_pos >= k_pos
            s = jnp.where(mask, s, NEG_INF)
            if ragged:
                # the padded tail of the last kv block holds no data:
                # zero it, so that 0 · garbage cannot reach the sums
                kv_idx = k_first + jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 0)
                v = jnp.where(kv_idx < t_kv, v, 0)
        m_prev = m_ref[...]            # (G·bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    run, crosses = jnp.bool_(True), jnp.bool_(False)
    if causal:
        q_last = q_offset + jnp.minimum((iq + 1) * block_q, t_q) - 1
        run = k_first <= q_last
        crosses = k_first + block_kv - 1 > q_first
    if ragged:
        crosses |= ik == n_kv - 1

    @pl.when(run & crosses)
    def _masked():
        step(True)

    @pl.when(run & ~crosses)
    def _full():
        step(False)

    @pl.when(ik == n_kv - 1)
    def _publish():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        for g in range(groups):
            o_ref[g] = o[g * block_q:(g + 1) * block_q].astype(o_ref.dtype)


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
    n_q, n_kv = pl.cdiv(Tq, bq), pl.cdiv(Tkv, bkv)
    grid = (B, Hk, n_q, n_kv)

    if causal:
        last = functools.partial(
            _last_kv_tile, block_q=bq, block_kv=bkv, t_q=Tq, n_kv=n_kv,
            q_offset=q_offset, minimum=jnp.minimum)

        def kv_tile(iq, ik):
            # past the q block's last needed tile the index stays put:
            # the pipeline then fetches nothing for the skipped steps
            return jnp.minimum(ik, last(iq))
    else:
        def kv_tile(iq, ik):
            return ik

    qf, kf, vf = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    q_spec = pl.BlockSpec((None, G, bq, Dh),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, bkv, Dh),
                           lambda b, h, iq, ik: (b, h, kv_tile(iq, ik), 0))

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv,
        groups=G, n_kv=n_kv, q_offset=q_offset, t_q=Tq, t_kv=Tkv,
    )
    sem = point.get("sched", "arbitrary")
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, Dh), q.dtype),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", sem),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pallas_interpret(interpret),
    )(qf, kf, vf)
    return out.transpose(0, 2, 1, 3)
