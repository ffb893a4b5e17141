"""Plain float32 reference of a Qwen3-MoE decoder, as one chip's share of
an expert-parallel deployment.

Pre-norm blocks of RMSNorm, GQA self-attention with RMSNorm over the
head dimension of q and k (QK-norm) before RoPE, RMSNorm and a
mixture-of-experts FFN; a final RMSNorm and an untied output head. The
router scores every expert of the layer (``expert_share.router_experts``)
in float32, keeps each token's top ``num_experts_per_tok`` and
renormalises their weights over them (``norm_topk_prob``). Written in
plain ``jax.numpy`` from the published description and nothing of the
program: no cache, no kernels, no sorting of tokens by expert. Attention,
RoPE, RMSNorm and the rounding of the control are the dense reference's
(``dense.py``).

Departures from the published description:

- RoPE rotates interleaved pairs of the head dimension, as the program
  does (see ``dense.py``). With QK-norm the same fixed permutation of
  the head dimension that maps HF's layout to this one applies to the
  ``q_norm`` and ``k_norm`` scales as it does to ``wq`` and ``wk``.
- The expert share: the chip holds ``num_experts`` experts of each layer,
  ``expert_share.first_expert`` and the ones after it. A token's choices
  of other experts are the other chips' part of the layer and are left
  out here, as in the program, so this is the part of the layer's output
  that this chip's experts give.

Every matmul runs in float32 at ``Precision.HIGHEST``, one layer at a
time, each layer upcasting its own bf16 weights.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from chipbench.reference import dense
from chipbench.reference.dense import (HIGHEST, causal_attention, dtype_bytes,
                                       rms_norm, rope)

ROW_BLOCK = 2048     # token rows per expert block


def dims(cfg: dict[str, Any]) -> dict[str, int]:
    """Sizes of the configuration under short names: E the router's
    width, Eh the experts held here from ``first`` on, k experts a token."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    share = cfg["expert_share"]
    return {
        "d": d, "H": H, "Hk": int(cfg["num_key_value_heads"]),
        "Dh": int(cfg.get("head_dim") or d // H),
        "ff": int(cfg["moe_intermediate_size"]),
        "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
        "E": int(share["router_experts"]), "Eh": int(cfg["num_experts"]),
        "first": int(share["first_expert"]),
        "k": int(cfg["num_experts_per_tok"]),
    }


def param_specs(cfg: dict[str, Any]) -> dict:
    """The weight tree: each leaf is ``(shape, mean, std)``.

    Matrices are drawn with mean 0 and a std of 1/sqrt(fan-in) (0.02 for
    the embedding), norm scales (QK-norm's included) as 1 + 0.1 N(0, 1),
    so that a program that skips a norm's scale reads different logits.
    """
    n = dims(cfg)
    d, H, Hk, Dh, ff, V, L, E, Eh = (n[k] for k in (
        "d", "H", "Hk", "Dh", "ff", "V", "L", "E", "Eh"))
    norm = (1.0, 0.1)
    return {
        "tok": {"embed": ((V, d), 0.0, 0.02),
                "unembed": ((d, V), 0.0, d ** -0.5)},
        "layers": {
            "ln1": ((L, d),) + norm,
            "attn": {"wq": ((L, d, H, Dh), 0.0, d ** -0.5),
                     "wk": ((L, d, Hk, Dh), 0.0, d ** -0.5),
                     "wv": ((L, d, Hk, Dh), 0.0, d ** -0.5),
                     "wo": ((L, H, Dh, d), 0.0, (H * Dh) ** -0.5),
                     "q_norm": ((L, Dh),) + norm,
                     "k_norm": ((L, Dh),) + norm},
            "ln2": ((L, d),) + norm,
            "ffn": {"router": ((L, d, E), 0.0, d ** -0.5),
                    "w_gate": ((L, Eh, d, ff), 0.0, d ** -0.5),
                    "w_up": ((L, Eh, d, ff), 0.0, d ** -0.5),
                    "w_down": ((L, Eh, ff, d), 0.0, ff ** -0.5)},
        },
        "ln_f": ((d,),) + norm,
    }


def quantize(kind: str) -> Callable[[str, jax.Array], jax.Array]:
    """The dense reference's rounding (``dense.quantize``), with the
    router as one more weight, scaled per expert."""
    rnd = dense.quantize(kind)

    def rnd_moe(name: str, x: jax.Array) -> jax.Array:
        return dense._round(x, kind, (0,)) if name == "router" else rnd(
            name, x)
    return rnd_moe


def experts(x: jax.Array, router: jax.Array, wg: jax.Array, wu: jax.Array,
            wd: jax.Array, *, k: int, first: int,
            rnd: Callable[[str, jax.Array], jax.Array]) -> jax.Array:
    """The held experts' part of the MoE FFN on x (B, T, d), float32.

    Each token's top-k over the whole router, weights renormalised over
    the k; each held expert e runs on every row and counts with the
    weight the row gave it (0 where it is not among the row's k).
    ``ROW_BLOCK`` rows at a time.
    """
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    rb = min(ROW_BLOCK, B * T)
    n = math.ceil(B * T / rb)
    rows = jnp.pad(rows, ((0, n * rb - B * T), (0, 0))).reshape(n, rb, d)
    router = rnd("router", router)

    def block(r):
        probs = jax.nn.softmax(jnp.dot(rnd("act", r), router,
                                       precision=HIGHEST), axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        ra = rnd("act", r)
        out = jnp.zeros_like(r)
        for e in range(wg.shape[0]):
            gate = jnp.sum(jnp.where(top_i == first + e, top_w, 0.0), -1)
            g = jnp.dot(ra, rnd("w_gate", wg[e]), precision=HIGHEST)
            u = jnp.dot(ra, rnd("w_up", wu[e]), precision=HIGHEST)
            y = jnp.dot(rnd("act", jax.nn.silu(g) * u), rnd("w_down", wd[e]),
                        precision=HIGHEST)
            out = out + gate[:, None] * y
        return out

    out = jax.lax.map(block, rows).reshape(n * rb, d)[:B * T]
    return out.reshape(B, T, d)


def _layer(h: jax.Array, layers: dict, i: jax.Array, *, eps: float,
           theta: float, k: int, first: int,
           rnd: Callable[[str, jax.Array], jax.Array]) -> jax.Array:
    """One decoder block on h (B, T, d), float32, with layer ``i``'s
    weights sliced from the stack and upcast here (a stack of experts is
    rounded one expert at a time, in ``experts``)."""
    def leaf(group: str | None, name: str) -> jax.Array:
        stack = layers[group][name] if group else layers[name]
        return jax.lax.dynamic_index_in_dim(
            stack, i, 0, keepdims=False).astype(jnp.float32)

    def w(group: str | None, name: str) -> jax.Array:
        return rnd(name, leaf(group, name))

    positions = jnp.arange(h.shape[1])
    x = rnd("act", rms_norm(h, w(None, "ln1"), eps))
    q = jnp.einsum("btd,dhk->bthk", x, w("attn", "wq"), precision=HIGHEST)
    kk = jnp.einsum("btd,dhk->bthk", x, w("attn", "wk"), precision=HIGHEST)
    v = jnp.einsum("btd,dhk->bthk", x, w("attn", "wv"), precision=HIGHEST)
    q = rope(rms_norm(q, w("attn", "q_norm"), eps), positions, theta, None)
    kk = rope(rms_norm(kk, w("attn", "k_norm"), eps), positions, theta, None)
    B, T, H, Dh = q.shape
    o = rnd("act", causal_attention(q, kk, v).reshape(B, T, H * Dh))
    h = h + jnp.einsum("btk,kd->btd", o,
                       w("attn", "wo").reshape(H * Dh, -1), precision=HIGHEST)
    x = rms_norm(h, w(None, "ln2"), eps)
    return h + experts(x, leaf("ffn", "router"), leaf("ffn", "w_gate"),
                       leaf("ffn", "w_up"), leaf("ffn", "w_down"),
                       k=k, first=first, rnd=rnd)


@functools.lru_cache(maxsize=8)
def _compiled(eps: float, theta: float, k: int, first: int, kind: str | None):
    rnd = quantize(kind) if kind else dense._exact
    layer = jax.jit(functools.partial(_layer, eps=eps, theta=theta, k=k,
                                      first=first, rnd=rnd))

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
    control: both operands of every weight matmul, the router's included,
    rounded to that type (weights per output channel, activations per
    token).
    """
    if cfg.get("rope_scaling"):
        raise ValueError(f"unsupported rope_scaling {cfg['rope_scaling']!r}")
    n = dims(cfg)
    embed, layer, head = _compiled(
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]), n["k"],
        n["first"], precision)
    with jax.default_matmul_precision("highest"):
        h = embed(params["tok"]["embed"], jnp.asarray(tokens, jnp.int32))
        for i in range(n["L"]):
            h = layer(h, params["layers"], jnp.int32(i))
        return head(h[:, -n_last:], params["ln_f"], params["tok"]["unembed"])


# ------------------------------------------------------------ work counts
# What the serving steps of this family need, from shapes, as in
# ``dense.py``. A token's expert rows are the (token, expert) pairs routed
# to held experts: k * Eh / E per token and layer under uniform routing. A
# decode step reads the weights of each held expert that one of its
# tokens is routed to: under uniform routing Eh * (1 - (1 - k/E)^B) of
# them per layer for B tokens.
def attn_params(cfg: dict[str, Any]) -> int:
    """A layer's weights outside its experts: attention, QK-norm, router
    and the two norms."""
    n = dims(cfg)
    attn = n["d"] * (n["H"] + 2 * n["Hk"]) * n["Dh"] + n["H"] * n["Dh"] * n["d"]
    return attn + 2 * n["Dh"] + n["d"] * n["E"] + 2 * n["d"]


def expert_params(cfg: dict[str, Any]) -> int:
    """One expert's weights: gate, up and down projections."""
    n = dims(cfg)
    return 3 * n["d"] * n["ff"]


def weight_bytes(cfg: dict[str, Any]) -> int:
    """Every weight the chip holds: layers, embedding, head, final norm."""
    n = dims(cfg)
    layer = attn_params(cfg) + n["Eh"] * expert_params(cfg)
    return (n["L"] * layer + 2 * n["V"] * n["d"] + n["d"]) * dtype_bytes(cfg)


def kv_bytes_per_token(cfg: dict[str, Any]) -> int:
    n = dims(cfg)
    return 2 * n["L"] * n["Hk"] * n["Dh"] * dtype_bytes(cfg)


def expert_rows_per_token(cfg: dict[str, Any]) -> float:
    """(token, held expert) rows per token, over all layers, expected
    under uniform routing."""
    n = dims(cfg)
    return n["L"] * n["k"] * n["Eh"] / n["E"]


def expert_reads(cfg: dict[str, Any], batch: int) -> float:
    """Held experts hit by a step of ``batch`` tokens, over all layers,
    expected under uniform routing."""
    n = dims(cfg)
    return n["L"] * n["Eh"] * (1.0 - (1.0 - n["k"] / n["E"]) ** batch)


def _dense_flops_per_token(cfg: dict[str, Any]) -> int:
    """Projections and the router, all layers (norms aside)."""
    n = dims(cfg)
    return 2 * n["L"] * (attn_params(cfg) - 2 * n["Dh"] - 2 * n["d"])


def prefill_flops(cfg: dict[str, Any], batch: int, seq: int) -> float:
    """Projections, router and the expected expert rows on every token,
    causal attention (QK^T and PV over the keys at or before each
    query), the head at the last position."""
    n = dims(cfg)
    per_token = (_dense_flops_per_token(cfg)
                 + 2 * expert_rows_per_token(cfg) * expert_params(cfg))
    attn = 4 * n["L"] * n["H"] * n["Dh"] * (seq * (seq + 1) // 2)
    head = 2 * n["d"] * n["V"]
    return batch * (seq * per_token + attn + head)


def decode_step(cfg: dict[str, Any], batch: int, length: int, *,
                rows: float | None = None,
                reads: float | None = None) -> tuple[float, float]:
    """(flops, bytes) of one decode step whose new token lands at position
    ``length - 1``, so that attention reads ``length`` cached positions.

    ``rows`` and ``reads`` are the step's expert rows and expert weight
    reads over all layers; by default those expected under uniform
    routing (``expert_rows_per_token``, ``expert_reads``).
    """
    n = dims(cfg)
    if rows is None:
        rows = batch * expert_rows_per_token(cfg)
    if reads is None:
        reads = expert_reads(cfg, batch)
    attn = 4 * n["L"] * n["H"] * n["Dh"] * length
    flops = (batch * (_dense_flops_per_token(cfg) + attn + 2 * n["d"] * n["V"])
             + 2 * rows * expert_params(cfg))
    b = dtype_bytes(cfg)
    weights = (n["L"] * attn_params(cfg) + reads * expert_params(cfg)
               + n["V"] * n["d"] + n["d"]) * b
    embed_rows = batch * n["d"] * b
    kv = batch * (length * kv_bytes_per_token(cfg)          # read
                  + kv_bytes_per_token(cfg))                # written
    return flops, weights + embed_rows + kv
