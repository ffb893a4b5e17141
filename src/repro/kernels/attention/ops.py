"""Attention ops: chunked-jnp flash implementation, dispatcher, compilette.

``flash_attention_jnp`` is the memory-efficient attention (O(T·d) live
memory, online softmax, double-checkpointed so the backward recomputes
score blocks) that training, the CPU, windowed attention and the
512-device dry run use. The serving prefill lowered for a TPU calls the
fused Pallas kernel ``flash_attention_pallas`` instead
(``prefill_attention``, called by ``models.layers``); the kernel is
validated against this path and ``attention_ref``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.compilette import Compilette
from repro.core.profiles import TPU_V5E, DeviceProfile
from repro.core.tuning_space import Param, Point, TuningSpace
from repro.kernels.attention.attention import (
    causal_tiles, flash_attention_pallas)
from repro.kernels.attention.ref import attention_ref
from repro.kernels.catalog import KernelDef, example_fill

NEG_INF = -1e30

DEFAULT_POINT: Point = {
    "block_q": 256, "block_kv": 512, "sched": "arbitrary", "lookahead": 1,
}


# ------------------------------------------------------- chunked jnp flash
@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "q_offset", "window", "q_chunk", "k_chunk",
        "scores_f32"),
)
def flash_attention_jnp(
    q: jax.Array,      # (B, Tq, H, Dh)
    k: jax.Array,      # (B, Tkv, Hk, Dh)
    v: jax.Array,      # (B, Tkv, Hk, Dh)
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    window: int | None = None,
    q_chunk: int = 256,
    k_chunk: int = 512,
    scores_f32: bool = True,
) -> jax.Array:
    B, Tq, H, Dh = q.shape
    _, Tk, Hk, _ = k.shape
    G = H // Hk
    scale = float(scale if scale is not None else Dh ** -0.5)
    qc = min(q_chunk, Tq)
    kc = min(k_chunk, Tk)
    n_q = math.ceil(Tq / qc)
    n_k = math.ceil(Tk / kc)
    Tq_p, Tk_p = n_q * qc, n_k * kc
    orig_dtype = q.dtype

    q = jnp.pad(q, ((0, 0), (0, Tq_p - Tq), (0, 0), (0, 0))) if Tq_p != Tq else q
    if Tk_p != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))

    # (n_q, B, Hk, G, qc, Dh) — kept in the input dtype: bf16 operands with
    # fp32 accumulation is the MXU fast path; scale is applied on the fp32
    # scores.
    qb = q.reshape(B, n_q, qc, Hk, G, Dh).transpose(1, 0, 3, 4, 2, 5)
    # (n_k, B, Hk, kc, Dh)
    kb = k.reshape(B, n_k, kc, Hk, Dh).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, n_k, kc, Hk, Dh).transpose(1, 0, 3, 2, 4)

    q_ids = jnp.arange(qc)
    k_ids = jnp.arange(kc)

    def per_q_chunk(_, inp):
        qcur, iq = inp

        def body(carry, kv_inp):
            m, l, acc = carry
            kblk, vblk, ik = kv_inp
            # scores_f32=False models the Pallas flash kernel's memory
            # profile in this jnp fallback: score blocks never leave VMEM
            # on TPU, so materializing them in bf16 here keeps the HBM
            # traffic estimate honest; softmax stats stay fp32 either way.
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qcur, kblk,
                preferred_element_type=(
                    jnp.float32 if scores_f32 else None),
            ).astype(jnp.float32) * scale
            q_pos = q_offset + iq * qc + q_ids[:, None]
            k_pos = ik * kc + k_ids[None, :]
            mask = k_pos < Tk
            if causal:
                mask &= q_pos >= k_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        init = (
            jnp.full((B, Hk, G, qc), NEG_INF, jnp.float32),
            jnp.zeros((B, Hk, G, qc), jnp.float32),
            jnp.zeros((B, Hk, G, qc, Dh), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(body), init, (kb, vb, jnp.arange(n_k))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(orig_dtype)

    _, outs = jax.lax.scan(
        jax.checkpoint(per_q_chunk), None, (qb, jnp.arange(n_q))
    )
    # outs: (n_q, B, Hk, G, qc, Dh) → (B, Tq, H, Dh)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Tq_p, H, Dh)
    return out[:, :Tq].astype(orig_dtype)


# ------------------------------------------------------------ decode path
def decode_attention(
    q: jax.Array,      # (B, 1, H, Dh) — one new token
    k: jax.Array,      # (B, S, Hk, Dh) KV cache
    v: jax.Array,
    *,
    length: jax.Array | int | None = None,
    scale: float | None = None,
    k_chunk: int = 4096,
) -> jax.Array:
    """Flash-decoding: online-softmax scan over KV chunks.

    Chunking bounds the live working set to one chunk (essential both on
    TPU and for the CPU dry-run, where XLA materializes bf16 math as f32 —
    a whole-cache op would double the cache's memory footprint).
    """
    B, Tq, H, Dh = q.shape
    _, S, Hk, _ = k.shape
    G = H // Hk
    scale = float(scale if scale is not None else Dh ** -0.5)
    qg = q.reshape(B, Tq, Hk, G, Dh)
    kc = min(k_chunk, S)
    n = math.ceil(S / kc)
    if n * kc != S:       # ragged tail: fall back to a single chunk
        kc, n = S, 1
    kb = k.reshape(B, n, kc, Hk, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n, kc, Hk, Dh).transpose(1, 0, 2, 3, 4)
    len_b = None if length is None else jnp.asarray(length).reshape(-1, 1)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, ik = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        if len_b is not None:
            k_pos = ik * kc + jnp.arange(kc)
            valid = k_pos[None, :] < len_b
            s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (
        jnp.full((B, Hk, G, Tq), NEG_INF, jnp.float32),
        jnp.zeros((B, Hk, G, Tq), jnp.float32),
        jnp.zeros((B, Hk, G, Tq, Dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, jnp.arange(n)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    # (B,Hk,G,Tq,Dh) -> (B,Tq,H,Dh)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dh)
    return o.astype(q.dtype)


# -------------------------------------------------------------- dispatcher
def attention(
    q, k, v, *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    window: int | None = None,
    impl: str = "chunked",
    point: Point | None = None,
):
    if impl == "chunked":
        p = dict(DEFAULT_POINT if point is None else point)
        return flash_attention_jnp(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            window=window, q_chunk=p["block_q"], k_chunk=p["block_kv"],
        )
    if impl == "ref":
        return attention_ref(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset, window=window
        )
    if impl == "pallas":
        if window is not None:
            raise NotImplementedError("pallas path: window masking not yet wired")
        p = dict(DEFAULT_POINT if point is None else point)
        return flash_attention_pallas(
            q, k, v, p, causal=causal, scale=scale, q_offset=q_offset,
        )
    raise ValueError(f"unknown attention impl {impl!r}")


# ------------------------------------------------- serving prefill dispatch
def prefill_kernel_serves(window: int | None, platform: str) -> bool:
    """Whether the serving prefill's causal attention, lowered for
    ``platform``, is the fused kernel: on a TPU and without a window,
    which the kernel does not mask."""
    return platform == "tpu" and window is None


def prefill_attention(q, k, v, *, window: int | None, q_chunk: int,
                      k_chunk: int, scores_f32: bool = True) -> jax.Array:
    """The serving prefill's causal attention: the fused kernel at
    (``q_chunk``, ``k_chunk``) tiles where ``prefill_kernel_serves``, the
    chunked jnp path elsewhere. The platform is the one the program is
    lowered for."""
    chunked = functools.partial(
        flash_attention_jnp, causal=True, window=window, q_chunk=q_chunk,
        k_chunk=k_chunk, scores_f32=scores_f32)
    if not prefill_kernel_serves(window, "tpu"):
        return chunked(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=chunked,
        tpu=functools.partial(
            flash_attention_pallas, interpret=False,
            point={"block_q": q_chunk, "block_kv": k_chunk}))


def prefill_kernel_tiles(cfg, platform: str, B: int, T: int,
                         blocks: tuple[int, int]) -> tuple[int, int]:
    """The fused kernel's tiles in one prefill of ``B`` sequences of
    ``T`` positions at ``blocks`` = (q_chunk, k_chunk), and of those the
    tiles it computes, summed over (attention layer, sequence, KV head)
    of model config ``cfg``; (0, 0) where the chunked jnp path serves."""
    if not prefill_kernel_serves(cfg.window, platform):
        return 0, 0
    grid, run = causal_tiles(T, T, *blocks)
    calls = cfg.attn_layers * B * cfg.n_kv_heads
    return calls * grid, calls * run


# ------------------------------------------------------------ tuning space
def make_space(
    Tq: int, Tkv: int, Dh: int,
    *,
    vmem_kb: int = TPU_V5E.vmem_kb,
) -> TuningSpace:
    params = (
        Param("block_q", (128, 256, 512), phase=1, switch_rank=0),
        Param("block_kv", (128, 256, 512, 1024), phase=1, switch_rank=1),
        Param("sched", ("arbitrary", "parallel"), phase=2),
        Param("lookahead", (0, 1, 2), phase=2),
    )

    def validator(p: Point) -> bool:
        bq, bkv = min(p["block_q"], Tq), min(p["block_kv"], Tkv)
        words = bq * Dh * 2 + 2 * bkv * Dh + bq * bkv + 2 * bq
        return words * 4 <= vmem_kb * 1024

    def no_leftover(p: Point) -> float:
        waste = 1.0
        for dim, blk in ((Tq, min(p["block_q"], Tq)), (Tkv, min(p["block_kv"], Tkv))):
            n = math.ceil(dim / blk)
            waste *= (n * blk) / dim
        return waste - 1.0

    return TuningSpace(params=params, validator=validator, no_leftover=no_leftover)


def attention_cost_model(
    point: Point, spec: dict[str, Any], profile: DeviceProfile
) -> float:
    B, Tq, Tkv, H, Dh = spec["B"], spec["Tq"], spec["Tkv"], spec["H"], spec["Dh"]
    causal = spec.get("causal", True)
    bq, bkv = min(point["block_q"], Tq), min(point["block_kv"], Tkv)
    words = bq * Dh * 2 + 2 * bkv * Dh + bq * bkv + 2 * bq
    if words * 4 > profile.vmem_kb * 1024:
        return float("inf")
    frac = 0.5 if causal else 1.0
    flops = 4.0 * B * H * Tq * Tkv * Dh * frac
    eff = bkv / (bkv + 128.0)
    compute_s = flops / (profile.peak_flops * eff)
    n_q = math.ceil(Tq / bq)
    bytes_total = (B * H * Tq * Dh + B * H * Tkv * Dh * n_q * 2) * 2.0
    mem_s = bytes_total / (profile.hbm_gbps * 1e9)
    steps = B * H * n_q * math.ceil(Tkv / bkv)
    overhead_s = steps * profile.grid_step_overhead_ns * 1e-9 * (
        0.8 if point["sched"] == "arbitrary" else 1.0)
    t = profile.exec_time_s(compute_s, mem_s, overhead_s)
    if not profile.overlap and point["lookahead"] > 0:
        t -= min(compute_s, mem_s) * min(0.35 * point["lookahead"], 0.7)
    return t


def make_attention_compilette(
    B: int, Tq: int, Tkv: int, H: int, Hk: int, Dh: int,
    *,
    causal: bool = True,
    vmem_kb: int = TPU_V5E.vmem_kb,
) -> Compilette:
    space = make_space(Tq, Tkv, Dh, vmem_kb=vmem_kb)

    def generate(point: Point, **spec: Any):
        @jax.jit
        def fn(q, k, v):
            return flash_attention_pallas(q, k, v, point, causal=causal)
        return fn

    def cost_model(point: Point, spec: dict[str, Any], profile: DeviceProfile) -> float:
        full = {"B": B, "Tq": Tq, "Tkv": Tkv, "H": H, "Dh": Dh, "causal": causal}
        full.update(spec)
        return attention_cost_model(point, full, profile)

    return Compilette("attention", space, generate, cost_model=cost_model)


# ---------------------------------------------------------- kernel catalog
def _catalog_generate(point: Point, spec: dict[str, Any], *,
                      interpret: bool | None = None):
    causal = bool(spec.get("causal", True))

    @jax.jit
    def fn(q, k, v):
        return flash_attention_pallas(q, k, v, point, causal=causal,
                                      interpret=interpret)
    return fn


def _extract_spec(q, k, v, **overrides: Any) -> dict[str, Any]:
    B, Tq, H, Dh = q.shape
    _, Tkv, Hk, _ = k.shape
    return {"B": int(B), "Tq": int(Tq), "Tkv": int(Tkv), "H": int(H),
            "Hk": int(Hk), "Dh": int(Dh), "causal": True,
            "dtype": str(q.dtype), **overrides}


def _shapes(spec: dict[str, Any]):
    dt = spec.get("dtype", "float32")
    q = (spec["B"], spec["Tq"], spec["H"], spec["Dh"])
    kv = (spec["B"], spec["Tkv"], spec["Hk"], spec["Dh"])
    return ((q, dt), (kv, dt), (kv, dt))


def _abstract_args(spec: dict[str, Any]) -> tuple:
    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in _shapes(spec))


def _example_args(spec: dict[str, Any]) -> tuple:
    # q and k at full amplitude keep the softmax far from uniform: near-
    # uniform weights average v's zero-mean ramp to almost nothing, and
    # an output that small would hide a wrong variant from the gate
    return tuple(example_fill(s, d, scale=scale)
                 for (s, d), scale in zip(_shapes(spec), (1.0, 1.0, 0.1)))


def _catalog_oracle(q, k, v):
    # the catalog registers causal attention only (_extract_spec pins
    # causal=True), so the oracle mirrors that fixed setting
    return attention_ref(q, k, v, causal=True)


KERNEL = KernelDef(
    name="attention",
    make_space=lambda spec: make_space(spec["Tq"], spec["Tkv"], spec["Dh"]),
    generate=_catalog_generate,
    cost_model=attention_cost_model,
    extract_spec=_extract_spec,
    abstract_args=_abstract_args,
    example_args=_example_args,
    default_point=DEFAULT_POINT,
    oracle=_catalog_oracle,
    # flash blocks re-scale every partial softmax sum vs the oracle's
    # single full-row softmax
    tolerance={"rtol": 2e-3, "atol": 1e-5},
)


__all__ = [
    "DEFAULT_POINT",
    "KERNEL",
    "causal_tiles",
    "flash_attention_jnp",
    "flash_attention_pallas",
    "prefill_attention",
    "prefill_kernel_serves",
    "prefill_kernel_tiles",
    "decode_attention",
    "attention",
    "attention_ref",
    "make_space",
    "make_attention_compilette",
    "attention_cost_model",
]
