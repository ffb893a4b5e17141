"""Plain float32 reference of a dense decoder (Llama-style, as DeepSeek LLM).

Pre-norm blocks of RMSNorm, GQA self-attention with RoPE, RMSNorm and a
SwiGLU MLP; a final RMSNorm and an untied output head. Written in plain
``jax.numpy`` from the configuration's published description and nothing
of the program: no cache, no kernels, no batching tricks. It reads the
same weights as the program, in the program's tree layout, which
:func:`param_specs` states.

Departures from the published description, each a layout and not a
change of the function class:

- RoPE rotates interleaved pairs ``(x[2i], x[2i+1])`` of the head
  dimension, as the program does; HF's Llama rotates the halves
  ``(x[i], x[i + Dh/2])``. The two are the same model up to a fixed
  permutation of the head dimension of ``wq`` and ``wk``.

Every matmul runs in float32 at ``Precision.HIGHEST``. The model is
computed one layer at a time, each layer upcasting its own bf16 weights,
so that a model whose float32 weights would not fit the chip still runs.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128        # query rows per attention block
ROW_BLOCK = 2048     # token rows per MLP block


def dims(cfg: dict[str, Any]) -> dict[str, int]:
    """Sizes of the configuration under short names."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "H": H, "Hk": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or d // H),
        "ff": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
    }


def param_specs(cfg: dict[str, Any]) -> dict:
    """The weight tree: each leaf is ``(shape, mean, std)``.

    Matrices are drawn with mean 0 and a std of 1/sqrt(fan-in) (0.02 for
    the embedding), norm scales as 1 + 0.1 N(0, 1), so that a program
    that skips a norm's scale reads different logits.
    """
    n = dims(cfg)
    d, H, Hk, Dh, ff, V, L = (n[k] for k in ("d", "H", "Hk", "Dh", "ff",
                                             "V", "L"))
    norm = (1.0, 0.1)
    return {
        "tok": {"embed": ((V, d), 0.0, 0.02),
                "unembed": ((d, V), 0.0, d ** -0.5)},
        "layers": {
            "ln1": ((L, d),) + norm,
            "attn": {"wq": ((L, d, H, Dh), 0.0, d ** -0.5),
                     "wk": ((L, d, Hk, Dh), 0.0, d ** -0.5),
                     "wv": ((L, d, Hk, Dh), 0.0, d ** -0.5),
                     "wo": ((L, H, Dh, d), 0.0, (H * Dh) ** -0.5)},
            "ln2": ((L, d),) + norm,
            "ffn": {"w_gate": ((L, d, ff), 0.0, d ** -0.5),
                    "w_up": ((L, d, ff), 0.0, d ** -0.5),
                    "w_down": ((L, ff, d), 0.0, ff ** -0.5)},
        },
        "ln_f": ((d,),) + norm,
    }


# Axes a weight is contracted over, per leaf name; the rest are its
# output channels (what a per-channel quantizer scales by).
CONTRACT_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
                 "w_gate": (0,), "w_up": (0,), "w_down": (0,),
                 "unembed": (0,)}


def _round(x: jax.Array, kind: str, axes: tuple[int, ...]) -> jax.Array:
    """x rounded to ``kind`` and back, scaled so that the absolute maximum
    over ``axes`` sits at the type's top (448 for fp8 e4m3, 127 for int8)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30)
    if kind == "fp8":
        q = (x * (448.0 / amax)).astype(jnp.float8_e4m3fn)
        return q.astype(jnp.float32) * (amax / 448.0)
    if kind == "int8":
        return jnp.clip(jnp.round(x * (127.0 / amax)), -127, 127) * (
            amax / 127.0)
    raise ValueError(f"unknown precision {kind!r}")


def quantize(kind: str) -> Callable[[str, jax.Array], jax.Array]:
    """The rounding of a matmul operand to ``kind`` and back.

    A weight (named as in ``CONTRACT_AXES``) is scaled per output
    channel; an activation (name ``"act"``) per row, over its last axis.
    Norm scales and the embedding table are left as they are; attention
    scores and softmax stay in float32.
    """
    def rnd(name: str, x: jax.Array) -> jax.Array:
        if name == "act":
            return _round(x, kind, (-1,))
        axes = CONTRACT_AXES.get(name)
        return x if axes is None else _round(x, kind, axes)
    return rnd


def _exact(name: str, x: jax.Array) -> jax.Array:
    return x


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: dict | None) -> jax.Array:
    """x: (B, T, heads, Dh); rotate interleaved pairs by position."""
    B, T, Hn, Dh = x.shape
    pos = positions.astype(jnp.float32)
    if scaling:
        if scaling.get("type", scaling.get("rope_type")) != "linear":
            raise ValueError(f"unsupported rope_scaling {scaling!r}")
        pos = pos / float(scaling["factor"])
    half = Dh // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos[:, None] * jnp.asarray(inv, jnp.float32)[None, :]    # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xp = x.reshape(B, T, Hn, half, 2)
    a, b = xp[..., 0], xp[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(B, T, Hn, Dh)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Softmax attention, query head h reading KV head h // (H / Hk).

    q: (B, T, H, Dh), k and v: (B, T, Hk, Dh). Queries are taken in
    blocks of ``Q_BLOCK`` rows against every key, so that the scores of
    one block are all that is held.
    """
    B, T, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    qb = min(Q_BLOCK, T)
    n = math.ceil(T / qb)
    qp = jnp.pad(q, ((0, 0), (0, n * qb - T), (0, 0), (0, 0)))
    qp = qp.reshape(B, n, qb, Hk, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    scale = 1.0 / math.sqrt(Dh)
    k_pos = jnp.arange(T)

    def block(args):
        qblk, i = args                                  # (B, qb, Hk, G, Dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, k,
                       precision=HIGHEST) * scale
        q_pos = i * qb + jnp.arange(qb)
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qp, jnp.arange(n)))      # (n, B, qb, Hk, G, Dh)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n * qb, H, Dh)
    return out[:, :T]


def swiglu_rows(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                rnd: Callable[[str, jax.Array], jax.Array]) -> jax.Array:
    """SwiGLU MLP over rows of x (B, T, d), ``ROW_BLOCK`` rows at a time;
    ``rnd("act", .)`` rounds each matmul's activation input."""
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    rb = min(ROW_BLOCK, B * T)
    n = math.ceil(B * T / rb)
    rows = jnp.pad(rows, ((0, n * rb - B * T), (0, 0))).reshape(n, rb, d)

    def block(r):
        r = rnd("act", r)
        g = jnp.dot(r, wg, precision=HIGHEST)
        u = jnp.dot(r, wu, precision=HIGHEST)
        return jnp.dot(rnd("act", jax.nn.silu(g) * u), wd, precision=HIGHEST)

    out = jax.lax.map(block, rows).reshape(n * rb, d)[:B * T]
    return out.reshape(B, T, d)


def _layer(h: jax.Array, layers: dict, i: jax.Array, *, eps: float,
           theta: float, scaling: dict | None,
           rnd: Callable[[str, jax.Array], jax.Array]) -> jax.Array:
    """One decoder block on h (B, T, d), float32, with layer ``i``'s
    weights sliced from the stack and upcast here."""
    def w(group: str | None, name: str) -> jax.Array:
        stack = layers[group][name] if group else layers[name]
        leaf = jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
        return rnd(name, leaf.astype(jnp.float32))

    positions = jnp.arange(h.shape[1])
    x = rnd("act", rms_norm(h, w(None, "ln1"), eps))
    q = jnp.einsum("btd,dhk->bthk", x, w("attn", "wq"), precision=HIGHEST)
    k = jnp.einsum("btd,dhk->bthk", x, w("attn", "wk"), precision=HIGHEST)
    v = jnp.einsum("btd,dhk->bthk", x, w("attn", "wv"), precision=HIGHEST)
    q = rope(q, positions, theta, scaling)
    k = rope(k, positions, theta, scaling)
    B, T, H, Dh = q.shape
    o = rnd("act", causal_attention(q, k, v).reshape(B, T, H * Dh))
    h = h + jnp.einsum("btk,kd->btd", o,
                       w("attn", "wo").reshape(H * Dh, -1), precision=HIGHEST)
    x = rms_norm(h, w(None, "ln2"), eps)
    return h + swiglu_rows(x, w("ffn", "w_gate"), w("ffn", "w_up"),
                           w("ffn", "w_down"), rnd)


@functools.lru_cache(maxsize=8)
def _compiled(eps: float, theta: float, scaling_key: str, kind: str | None):
    import json
    scaling = json.loads(scaling_key)
    rnd = quantize(kind) if kind else _exact
    layer = jax.jit(functools.partial(_layer, eps=eps, theta=theta,
                                      scaling=scaling, rnd=rnd))

    @jax.jit
    def head(h, ln_f, unembed):
        x = rnd("act", rms_norm(h, ln_f.astype(jnp.float32), eps))
        return jnp.einsum("btd,dv->btv", x,
                          rnd("unembed", unembed.astype(jnp.float32)),
                          precision=HIGHEST)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    return embed, layer, head


def last_logits(params: dict, tokens: Any, cfg: dict[str, Any], n_last: int,
                *, precision: str | None = None) -> jax.Array:
    """Float32 logits at the last ``n_last`` positions of ``tokens`` (B, S).

    ``precision`` None is the reference; ``"fp8"`` or ``"int8"`` is the
    control: both operands of every weight matmul rounded to that type
    (weights per output channel, activations per token).
    """
    import json
    embed, layer, head = _compiled(
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        json.dumps(cfg.get("rope_scaling"), sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        h = embed(params["tok"]["embed"], jnp.asarray(tokens, jnp.int32))
        for i in range(dims(cfg)["L"]):
            h = layer(h, params["layers"], jnp.int32(i))
        return head(h[:, -n_last:], params["ln_f"], params["tok"]["unembed"])


# ------------------------------------------------------------ work counts
# What the serving steps of this family need, from shapes: causal
# attention counts only the keys at or before each query, the prefill
# head runs at the last position only, and a decode step reads each
# weight once, the embedding rows it looks up, and the K/V cache up to
# each sequence's position. A program that computes more (masked blocks,
# a whole padded cache) reads as a lower share of the peak.
def dtype_bytes(cfg: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]


def layer_params(cfg: dict[str, Any]) -> int:
    n = dims(cfg)
    attn = n["d"] * (n["H"] + 2 * n["Hk"]) * n["Dh"] + n["H"] * n["Dh"] * n["d"]
    return attn + 3 * n["d"] * n["ff"] + 2 * n["d"]


def weight_bytes(cfg: dict[str, Any]) -> int:
    """Every weight the chip holds: layers, embedding, head, final norm."""
    n = dims(cfg)
    params = n["L"] * layer_params(cfg) + 2 * n["V"] * n["d"] + n["d"]
    return params * dtype_bytes(cfg)


def kv_bytes_per_token(cfg: dict[str, Any]) -> int:
    n = dims(cfg)
    return 2 * n["L"] * n["Hk"] * n["Dh"] * dtype_bytes(cfg)


def _matmul_flops_per_token(cfg: dict[str, Any]) -> int:
    n = dims(cfg)
    return 2 * n["L"] * (layer_params(cfg) - 2 * n["d"])


def prefill_flops(cfg: dict[str, Any], batch: int, seq: int) -> int:
    """Projections and MLP on every token, causal attention (QK^T and PV
    over the keys at or before each query), the head at the last position."""
    n = dims(cfg)
    attn = 4 * n["L"] * n["H"] * n["Dh"] * (seq * (seq + 1) // 2)
    head = 2 * n["d"] * n["V"]
    return batch * (seq * _matmul_flops_per_token(cfg) + attn + head)


def decode_step(cfg: dict[str, Any], batch: int, length: int) -> tuple[int, int]:
    """(flops, bytes) of one decode step whose new token lands at position
    ``length - 1``, so that attention reads ``length`` cached positions."""
    n = dims(cfg)
    attn = 4 * n["L"] * n["H"] * n["Dh"] * length
    flops = batch * (_matmul_flops_per_token(cfg) + attn + 2 * n["d"] * n["V"])
    b = dtype_bytes(cfg)
    layers_and_head = (n["L"] * layer_params(cfg) + n["V"] * n["d"]
                       + n["d"]) * b
    embed_rows = batch * n["d"] * b
    kv = batch * (length * kv_bytes_per_token(cfg)          # read
                  + kv_bytes_per_token(cfg))                # written
    return flops, layers_and_head + embed_rows + kv
