"""Mixture-of-Experts FFN: one device's share of an expert-parallel layer,
dropless.

The router scores all ``n_experts`` experts of the layer in float32 and
keeps each token's ``top_k``, with their weights renormalised over the
k. The device holds ``n_held_experts`` of the experts, ``first_expert``
and the ones after it. Each (token, choice) routed to a held expert is
computed; the other choices are the other devices' part of the layer and
are left out here. With every expert held this is the whole layer.

Nothing is dropped for capacity. Tokens go through in blocks of
``BLOCK_TOKENS``; a block's rows (a token's held choices, at most
min(top_k, held) of them) are sorted by expert and multiplied with
``jax.lax.ragged_dot``, so each expert computes exactly the rows routed
to it and the result does not depend on how tokens are grouped: a
token's output is the same in a long prefill and in a one-token decode.

A Switch/GShard load-balancing auxiliary loss over the full router is
returned, and with it the step's routing counts (:func:`moe_ffn`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models.params import ParamDef
from repro.models import layers as L

# Tokens per dispatch block: bounds the rows gathered at once (a block's
# sorted rows are BLOCK_TOKENS * min(top_k, held) rows of d_model).
BLOCK_TOKENS = 4096


def moe_defs(cfg: ModelConfig) -> dict:
    d, ff, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_held_experts
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff)
    defs = {
        "router": ParamDef((d, E), ("embed", None), scale=s_in),
        "w_gate": ParamDef((Eh, d, ff), ("expert", "embed", None), scale=s_in),
        "w_up": ParamDef((Eh, d, ff), ("expert", "embed", None), scale=s_in),
        "w_down": ParamDef((Eh, ff, d), ("expert", None, "embed"), scale=s_out),
    }
    if cfg.n_shared_experts:
        defs["shared"] = L.mlp_defs(
            cfg, d_ff=cfg.d_ff * cfg.n_shared_experts
        )
    return defs


@jax.named_scope("moe.route")
def route(x: jax.Array, router: jax.Array, cfg: ModelConfig):
    """x: (N, d) → (weights (N, k) f32, held expert ids (N, k), held
    (N, k) bool, aux loss). Ids count from ``first_expert``."""
    E, k = cfg.n_experts, cfg.top_k
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (N, E)
    gate_w, gate_idx = jax.lax.top_k(probs, k)                  # (N, k)
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)
    # Switch/GShard load-balancing aux loss over the whole router
    me = jnp.mean(probs, axis=0)                                        # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1),
        axis=0) / k                                                     # (E,)
    aux = E * jnp.sum(me * ce)
    local = gate_idx - cfg.first_expert
    held = (local >= 0) & (local < cfg.n_held_experts)
    return gate_w, local, held, aux


def _block(x: jax.Array, w: jax.Array, local: jax.Array, held: jax.Array,
           p: dict, cfg: ModelConfig, layer: jax.Array | None) -> jax.Array:
    """The held experts' part of the output of n tokens x: (n, d)."""
    n, d = x.shape
    Eh = cfg.n_held_experts
    R = min(cfg.top_k, Eh)        # held choices a token can have
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    groups = Eh
    if layer is not None:         # every layer's experts: pick this one's
        groups = wg.shape[0] * Eh
        wg, wu, wd = (a.reshape((groups,) + a.shape[2:])
                      for a in (wg, wu, wd))
        local = local + layer * Eh
    with jax.named_scope("moe.dispatch"):
        if R < cfg.top_k:         # a token's held choices first, the rest go
            order = jnp.argsort(~held, axis=1, stable=True)[:, :R]
            w, local, held = (jnp.take_along_axis(a, order, axis=1)
                              for a in (w, local, held))
        key = jnp.where(held, local, groups).reshape(n * R)  # left out: last
        perm = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((groups + 1,), jnp.int32).at[key].add(1)[:groups]
        rows = x[perm // R]                                     # (n*R, d)
    with jax.named_scope("moe.experts"):
        g = jax.lax.ragged_dot(rows, wg.astype(x.dtype), sizes)
        u = jax.lax.ragged_dot(rows, wu.astype(x.dtype), sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, wd.astype(x.dtype), sizes)
    with jax.named_scope("moe.combine"):
        inv = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(n * R, dtype=perm.dtype))
        # rows past the groups are not computed: zero them, never weigh them
        y = jnp.where(held[..., None], y[inv].reshape(n, R, d), 0)
        return jnp.einsum("nr,nrd->nd", w.astype(x.dtype), y)


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            layer: jax.Array | None = None,
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, T, d) → (out, aux_loss, counts).

    With ``layer``, p's expert weights are the stacks of every layer
    (L, held, ...) and layer ``layer``'s experts compute: no slice of
    them is made.

    ``counts`` (int32, 2) holds the (token, expert) rows routed to held
    experts and the held experts hit at least once: the expert weight
    reads the step needs. Callers that do not read them lose them to
    dead-code elimination.
    """
    B, T, d = x.shape
    N = B * T
    Eh = cfg.n_held_experts
    xf = x.reshape(N, d)
    w, local, held, aux = route(xf, p["router"], cfg)
    hits = jnp.zeros((Eh + 1,), jnp.int32).at[
        jnp.where(held, local, Eh)].add(1)[:Eh]
    counts = jnp.stack([jnp.sum(hits), jnp.sum(hits > 0)]).astype(jnp.int32)

    nb = min(BLOCK_TOKENS, N)
    blocks = -(-N // nb)
    if blocks == 1:
        out = _block(xf, w, local, held, p, cfg, layer)
    else:                 # pad to whole blocks; padding is held by no one
        pad = blocks * nb - N

        def split(a):
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape((blocks, nb) + a.shape[1:])

        out = jax.lax.map(
            lambda a: _block(*a, p, cfg, layer),
            (split(xf), split(w), split(local), split(held)),
        ).reshape(blocks * nb, d)[:N]
    out = out.reshape(B, T, d)
    if cfg.n_shared_experts:
        out = out + L.mlp(x, p["shared"], cfg)
    return shard(out, "batch", "seq", "embed"), aux, counts
