"""Shared model layers: norms, RoPE/M-RoPE, GQA attention, MLPs, embeddings.

All layers are pure functions over param dicts (declared via ParamDef).
RoPE uses the interleaved-pair convention: the head dim is viewed as
(Dh//2, 2) pairs so sharding the head dim never splits a rotation pair.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.kernels.attention.ops import (
    decode_attention, flash_attention_jnp, prefill_attention)
from repro.models.params import ParamDef
from repro.runtime.kernel_plane import active_plane


# ------------------------------------------------------------ kernel plane
def _plane_routes(*arrays: jax.Array):
    """The active kernel-tuning plane, when these EAGER arrays can route.

    Inside a jit trace the arguments are tracers: the coordinator-managed
    handle (a python-level function-pointer swap) cannot run there, so
    traced call sites instead adopt the plane's best-known points (see
    :func:`plane_attn_chunks`) and keep the pure-jnp kernel body.
    """
    plane = active_plane()
    if plane is None:
        return None
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        return None
    return plane


def plane_attn_chunks(cfg: ModelConfig) -> tuple[int, int]:
    """Attention chunk sizes: the plane's tuned blocks, else cfg defaults.

    This is the trace-time half of kernel-granular tuning: a jitted
    step-program generated while a plane is active inherits the
    attention kernel's independently tuned ``block_q``/``block_kv``
    instead of the config's hard-coded chunk sizes (warm-started
    registries make this bite from the very first trace of a restarted
    process).
    """
    plane = active_plane()
    if plane is not None and plane.adopt_points:
        best = plane.best_point("attention")
        if best is not None:
            return (int(best.get("block_q", cfg.attn_q_chunk)),
                    int(best.get("block_kv", cfg.attn_k_chunk)))
    return cfg.attn_q_chunk, cfg.attn_k_chunk


def plane_decode_chunk(cfg: ModelConfig) -> int:
    """Flash-decoding KV chunk: the plane's tuned ``k_chunk``, else cfg's.

    Trace-time adoption for the decode path: a jitted decode step traced
    while a plane is active inherits the ``decode_attention`` kernel's
    independently tuned chunk (per cache-length bucket) instead of the
    hard-coded ``cfg.decode_k_chunk`` — suppressed, like the attention
    chunks, when a program-level tuner owns the knob ("both" mode).
    """
    plane = active_plane()
    if plane is not None and plane.adopt_points:
        best = plane.best_point("decode_attention")
        if best is not None:
            return int(best.get("k_chunk", cfg.decode_k_chunk))
    return cfg.decode_k_chunk


# ----------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    plane = _plane_routes(x, scale)
    if plane is not None and eps == 1e-6 and x.ndim >= 2:
        # coordinator-managed handle: the fused Pallas kernel tuned as an
        # independent unit (block_rows its own space, own strategy)
        shape = x.shape
        y = plane.call("rmsnorm", x.reshape(-1, shape[-1]), scale)
        if y is not None:
            return y.reshape(shape)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("norm")
def norm(x: jax.Array, scale: jax.Array, kind: str) -> jax.Array:
    return rms_norm(x, scale) if kind == "rmsnorm" else layer_norm(x, scale)


# ------------------------------------------------------------------ rope
def rope_freqs(d_head: int, theta: float) -> jax.Array:
    half = d_head // 2
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


@jax.named_scope("attn.rope")
def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, Dh); positions: (B, T) int32. Interleaved pairs."""
    B, T, H, Dh = x.shape
    freqs = rope_freqs(Dh, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, T, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]                   # (B, T, 1, Dh/2)
    sin = jnp.sin(ang)[:, :, None, :]
    xp = x.astype(jnp.float32).reshape(B, T, H, Dh // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, T, H, Dh).astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float,
    sections: tuple[int, ...],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE. positions: (3, B, T) for (t, h, w).

    The Dh/2 frequency pairs are split into len(sections) groups; group i
    rotates by positions[i].
    """
    B, T, H, Dh = x.shape
    half = Dh // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(Dh, theta)                       # (half,)
    # Select which positional stream drives each frequency pair.
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections),
        total_repeat_length=half,
    )                                                   # (half,)
    pos = positions.astype(jnp.float32)[sec_id]         # (half, B, T)
    ang = jnp.einsum("fbt,f->btf", pos, freqs)          # (B, T, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xp = x.astype(jnp.float32).reshape(B, T, H, half, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(B, T, H, Dh).astype(x.dtype)


def sinusoidal_embedding(T: int, d: int) -> jax.Array:
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------- attention
def attention_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, H, Hk, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    defs = {
        "wq": ParamDef((d, H, Dh), ("embed", "heads", None),
                       scale=1.0 / math.sqrt(d)),
        "wk": ParamDef((d, Hk, Dh), ("embed", "kv", None),
                       scale=1.0 / math.sqrt(d)),
        "wv": ParamDef((d, Hk, Dh), ("embed", "kv", None),
                       scale=1.0 / math.sqrt(d)),
        "wo": ParamDef((H, Dh, d), ("heads", None, "embed"),
                       scale=1.0 / math.sqrt(H * Dh)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, Dh), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((Hk, Dh), ("kv", None), init="zeros")
        defs["bv"] = ParamDef((Hk, Dh), ("kv", None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((Dh,), (None,), init="ones")
        defs["k_norm"] = ParamDef((Dh,), (None,), init="ones")
    return defs


@jax.named_scope("attn.qkv")
def qkv_proj(x: jax.Array, p: dict, cfg: ModelConfig):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:       # per head, over head_dim, before RoPE
        with jax.named_scope("attn.qk_norm"):
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv", None)
    v = shard(v, "batch", "seq", "kv", None)
    return q, k, v


@jax.named_scope("attn.out")
def attn_out(o: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"].astype(o.dtype))
    return shard(out, "batch", "seq", "embed")


def self_attention(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    q_offset: int = 0,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder)."""
    q, k, v = qkv_proj(x, p, cfg)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif positions is not None:
        pos2d = positions if positions.ndim == 2 else positions[None]
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
    plane = _plane_routes(q, k, v)
    o = None
    if (plane is not None and causal and q_offset == 0
            and cfg.window is None):
        # eager call with an active plane: the flash kernel runs as an
        # independently tuned coordinator-managed unit
        o = plane.call("attention", q, k, v)
    if o is None:
        qc, kc = plane_attn_chunks(cfg)
        with jax.named_scope("attn.core"):
            o = flash_attention_jnp(
                q, k, v, causal=causal, q_offset=q_offset,
                window=cfg.window, q_chunk=qc, k_chunk=kc,
                scores_f32=cfg.attn_scores_f32,
            )
    return attn_out(o, p, cfg)


def self_attention_with_cache(
    x: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Prefill: returns output and the (k, v) cache to keep."""
    q, k, v = qkv_proj(x, p, cfg)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif positions is not None:
        pos2d = positions if positions.ndim == 2 else positions[None]
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
    qc, kc = plane_attn_chunks(cfg)
    with jax.named_scope("attn.core"):
        # on a TPU the fused kernel: score tiles stay in VMEM and tiles
        # above the diagonal are skipped
        o = prefill_attention(q, k, v, window=cfg.window, q_chunk=qc,
                              k_chunk=kc, scores_f32=cfg.attn_scores_f32)
    return attn_out(o, p, cfg), (k, v)


def to_bits(x: jax.Array) -> jax.Array:
    """bf16 → u16 bit view (exact; no-op for other dtypes).

    Used around scan-collected KV caches so XLA:CPU's float normalization
    cannot rewrite the internal ys dynamic-update-slice in f32 (which would
    double the dry-run cache footprint). Not free on TPU: a view of a whole
    stacked cache fixes the scan carry's tile layout, at the cost of a full
    pass over the cache and a copy into and out of that layout per step
    (0.93-1.33 s for the view alone in a 10 s traced slice of deepseek-7b
    decoding on a v5e), so the transformer decode carries its bf16 caches
    as they are."""
    return jax.lax.bitcast_convert_type(x, jnp.uint16) \
        if x.dtype == jnp.bfloat16 else x


def from_bits(x: jax.Array, like_dtype=jnp.bfloat16) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, like_dtype) \
        if x.dtype == jnp.uint16 else x


@jax.named_scope("attn.kv_update")
def _dus_bits(cache: jax.Array, update: jax.Array, start: tuple) -> jax.Array:
    """dynamic_update_slice through a u16 bit-view for bf16 caches.

    XLA:CPU's float-normalization otherwise rewrites the bf16 DUS in f32,
    materializing an f32 copy of the whole cache in the dry-run memory
    analysis. The bit view is exact and a no-op on TPU.
    """
    if cache.dtype == jnp.bfloat16:
        c = jax.lax.bitcast_convert_type(cache, jnp.uint16)
        u = jax.lax.bitcast_convert_type(update.astype(jnp.bfloat16), jnp.uint16)
        out = jax.lax.dynamic_update_slice(c, u, start)
        return jax.lax.bitcast_convert_type(out, jnp.bfloat16)
    return jax.lax.dynamic_update_slice(cache, update.astype(cache.dtype), start)


def decode_self_attention(
    x: jax.Array,                    # (B, 1, d)
    p: dict,
    cfg: ModelConfig,
    cache_k: jax.Array,              # (B, S, Hk, Dh)
    cache_v: jax.Array,
    pos: jax.Array,                  # scalar int32: cache write slot
    rope_pos: jax.Array | None = None,   # rotary position (defaults to pos;
                                         # differs for VLM, where vision
                                         # patches share a grid position)
):
    """One-token decode against a KV cache (in-place cache update)."""
    q, k, v = qkv_proj(x, p, cfg)
    B = x.shape[0]
    positions = jnp.full((B, 1), rope_pos if rope_pos is not None else pos,
                         jnp.int32)
    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(positions[None], (3, B, 1))
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    S = cache_k.shape[1]
    if cfg.window is not None and cfg.window < S:
        slot = pos % cfg.window
        S_eff = cfg.window
    else:
        slot = pos
        S_eff = S
    cache_k = _dus_bits(cache_k, k, (0, slot, 0, 0))
    cache_v = _dus_bits(cache_v, v, (0, slot, 0, 0))
    cache_k = shard(cache_k, "batch", "kv_seq", "kv", "kv_dh")
    cache_v = shard(cache_v, "batch", "kv_seq", "kv", "kv_dh")
    length = jnp.minimum(pos + 1, S_eff)
    plane = _plane_routes(q, cache_k, cache_v)
    o = None
    if plane is not None:
        # eager call with an active plane: flash-decoding runs as an
        # independently tuned unit, keyed per cache-length bucket
        o = plane.call("decode_attention", q, cache_k, cache_v,
                       jnp.asarray(length, jnp.int32))
    if o is None:
        with jax.named_scope("attn.core"):
            o = decode_attention(q, cache_k, cache_v, length=length,
                                 k_chunk=plane_decode_chunk(cfg))
    return attn_out(o, p, cfg), (cache_k, cache_v)


def cross_attention_defs(cfg: ModelConfig) -> dict:
    return attention_defs(cfg)


def cross_attention(
    x: jax.Array, p: dict, cfg: ModelConfig,
    enc_k: jax.Array, enc_v: jax.Array,
) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V."""
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"].astype(x.dtype))
    if x.shape[1] == 1:
        o = decode_attention(q, enc_k, enc_v,
                             k_chunk=plane_decode_chunk(cfg))
    else:
        o = flash_attention_jnp(
            q, enc_k, enc_v, causal=False,
            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk)
    return attn_out(o, p, cfg)


def encoder_kv(p: dict, cfg: ModelConfig, enc_out: jax.Array):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"].astype(enc_out.dtype))
    return k, v


# ------------------------------------------------------------------- mlp
def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff)
    if cfg.act == "swiglu":
        return {
            "w_gate": ParamDef((d, ff), ("embed", "ffn"), scale=s_in),
            "w_up": ParamDef((d, ff), ("embed", "ffn"), scale=s_in),
            "w_down": ParamDef((ff, d), ("ffn", "embed"), scale=s_out),
        }
    return {
        "w_up": ParamDef((d, ff), ("embed", "ffn"), scale=s_in),
        "w_down": ParamDef((ff, d), ("ffn", "embed"), scale=s_out),
    }


@jax.named_scope("mlp")
def mlp(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "swiglu":
        g = jnp.einsum("btd,df->btf", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("btd,df->btf", x, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("btd,df->btf", x, p["w_up"].astype(x.dtype))
        h = jax.nn.gelu(h) if cfg.act == "gelu" else jnp.square(jax.nn.relu(h))
    h = shard(h, "batch", "seq", "ffn")
    out = jnp.einsum("btf,fd->btd", h, p["w_down"].astype(x.dtype))
    return shard(out, "batch", "seq", "embed")


# ------------------------------------------------------------- embeddings
def embedding_defs(cfg: ModelConfig) -> dict:
    return {
        "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "unembed": ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                            scale=1.0 / math.sqrt(cfg.d_model)),
    }


@jax.named_scope("embed")
def embed_tokens(tokens: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    x = p["embed"].astype(cfg.compute_dtype)[tokens]
    return shard(x, "batch", "seq", "embed")


@jax.named_scope("head")
def logits_out(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    logits = jnp.einsum("btd,dv->btv", x, p["unembed"].astype(x.dtype))
    logits = shard(logits, "batch", "seq", "vocab")
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def cross_entropy(
    logits: jax.Array,      # (B, T, V)
    labels: jax.Array,      # (B, T) int32
    mask: jax.Array | None = None,
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
