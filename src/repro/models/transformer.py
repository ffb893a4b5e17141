"""Decoder-only transformer (dense / MoE / VLM backbones).

Layers are stacked (leading L axis) and iterated with ``jax.lax.scan`` so
HLO size is O(1) in depth — essential for compiling 48–64-layer models on
the 512-device dry-run host. Per-layer remat policy is configurable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.moe import moe_defs, moe_ffn
from repro.models.params import ParamDef, cast_params


def stack_defs(defs: dict, n: int) -> dict:
    """Prepend a stacked 'layers' axis to every ParamDef in the tree."""
    out = {}
    for name, node in defs.items():
        if isinstance(node, ParamDef):
            out[name] = ParamDef(
                (n,) + node.shape, ("layers",) + node.axes, node.init, node.scale
            )
        else:
            out[name] = stack_defs(node, n)
    return out


def layer_defs(cfg: ModelConfig) -> dict:
    defs = {
        "ln1": ParamDef((cfg.d_model,), (None,), init="ones"),
        "attn": L.attention_defs(cfg),
    }
    if not cfg.parallel_block:
        defs["ln2"] = ParamDef((cfg.d_model,), (None,), init="ones")
    defs["ffn"] = moe_defs(cfg) if cfg.family == "moe" else L.mlp_defs(cfg)
    return defs


def transformer_defs(cfg: ModelConfig) -> dict:
    return {
        "tok": L.embedding_defs(cfg),
        "layers": stack_defs(layer_defs(cfg), cfg.n_layers),
        "ln_f": ParamDef((cfg.d_model,), (None,), init="ones"),
    }


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )


def _ffn_apply(x, lp, cfg, layer=None
               ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """(out, aux loss, routing counts); dense layers count nothing."""
    if cfg.family == "moe":
        return moe_ffn(x, lp["ffn"], cfg, layer=layer)
    return L.mlp(x, lp["ffn"], cfg), jnp.zeros((), jnp.float32), None


def _serve_layers(layers: dict, cfg: ModelConfig):
    """The serving scans' per-layer inputs, and ``unpack`` giving one
    layer's params and its index into whole expert stacks (None: dense).

    A MoE layer's expert weights reach every layer whole rather than as
    the scan's slice, and the layer picks its own experts among all
    layers' (``moe_ffn``'s ``layer``): the ragged dot is a kernel, and XLA
    copies a sliced operand of a kernel (every held expert of the layer,
    each step) where a dot reads its slice in place.
    """
    if cfg.family != "moe":
        return layers, lambda lp: (lp, None)
    ffn = dict(layers["ffn"])
    stacks = {k: ffn.pop(k) for k in ("w_gate", "w_up", "w_down")}

    def unpack(inp):
        lp, i = inp
        return dict(lp, ffn=dict(lp["ffn"], **stacks)), i

    return (dict(layers, ffn=ffn), jnp.arange(cfg.n_layers)), unpack


# ---------------------------------------------------------------- forward
def forward_train(params, x, positions, cfg: ModelConfig):
    """x: (B, T, d) embedded input → (final hidden, aux loss)."""

    def body(carry, lp):
        h, aux = carry
        hn = L.norm(h, lp["ln1"], cfg.norm)
        attn = L.self_attention(hn, lp["attn"], cfg, positions=positions)
        if cfg.parallel_block:
            f, a, _ = _ffn_apply(hn, lp, cfg)
            h = h + attn + f
        else:
            h = h + attn
            f, a, _ = _ffn_apply(L.norm(h, lp["ln2"], cfg.norm), lp, cfg)
            h = h + f
        h = shard(h, "batch", "seq", "embed")
        return (h, aux + a), None

    (x, aux), _ = jax.lax.scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return L.norm(x, params["ln_f"], cfg.norm), aux


def forward_prefill(params, x, positions, cfg: ModelConfig):
    """Causal forward that also returns stacked (L, B, T, Hk, Dh) KV caches."""

    xs, unpack = _serve_layers(params["layers"], cfg)

    def body(carry, inp):
        h = carry
        lp, layer = unpack(inp)
        hn = L.norm(h, lp["ln1"], cfg.norm)
        attn, (k, v) = L.self_attention_with_cache(
            hn, lp["attn"], cfg, positions=positions)
        if cfg.parallel_block:
            f, _, _ = _ffn_apply(hn, lp, cfg, layer)
            h = h + attn + f
        else:
            h = h + attn
            f, _, _ = _ffn_apply(L.norm(h, lp["ln2"], cfg.norm), lp, cfg,
                                 layer)
            h = h + f
        h = shard(h, "batch", "seq", "embed")
        return h, (k, v)

    x, (ks, vs) = jax.lax.scan(_remat(body, cfg), x, xs)
    return L.norm(x, params["ln_f"], cfg.norm), (ks, vs)


def forward_decode(params, x, cache, pos, cfg: ModelConfig, rope_pos=None,
                   counts: bool = False):
    """One-token decode. x: (B, 1, d); cache: (k, v) with leading L axis.

    The stacked caches ride the scan *carry* as they are, and each layer
    updates its slice in place: with the cache donated (``decode_step``'s
    argument 1) the carry is one buffer end to end, aliased with the
    input. ys would double-buffer 2×cache bytes; a bit view of the whole
    stacked cache would give the carry a padded tile layout on TPU and a
    full copy of the cache into it and out of it each step.

    With ``counts`` (MoE) the carry also sums the layers' routing counts
    (``moe_ffn``), returned third.
    """
    ks, vs = cache
    xs, unpack = _serve_layers(params["layers"], cfg)

    def body(carry, inp):
        h, ks, vs = carry[:3]
        lp, i = inp
        lp, layer = unpack(lp)
        with jax.named_scope("cache.read"):
            ck = jax.lax.dynamic_index_in_dim(ks, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(vs, i, 0, keepdims=False)
        hn = L.norm(h, lp["ln1"], cfg.norm)
        attn, (ck, cv) = L.decode_self_attention(
            hn, lp["attn"], cfg, ck, cv, pos, rope_pos=rope_pos)
        if cfg.parallel_block:
            f, _, c = _ffn_apply(hn, lp, cfg, layer)
            h = h + attn + f
        else:
            h = h + attn
            f, _, c = _ffn_apply(L.norm(h, lp["ln2"], cfg.norm), lp, cfg,
                                 layer)
            h = h + f
        with jax.named_scope("cache.write"):
            ks = jax.lax.dynamic_update_index_in_dim(ks, ck, i, 0)
            vs = jax.lax.dynamic_update_index_in_dim(vs, cv, i, 0)
        if counts:
            return (h, ks, vs, carry[3] + c), None
        return (h, ks, vs), None

    init = (x, ks, vs)
    if counts:
        init += (jnp.zeros((2,), jnp.int32),)
    out, _ = jax.lax.scan(body, init, (xs, jnp.arange(cfg.n_layers)))
    h = L.norm(out[0], params["ln_f"], cfg.norm)
    if counts:
        return h, (out[1], out[2]), out[3]
    return h, (out[1], out[2])


# ------------------------------------------------------------------ model
class TransformerLM:
    """Dense/MoE decoder LM with the standard step functions."""

    def __init__(self, cfg: ModelConfig) -> None:
        self.cfg = cfg

    # --- params ---
    def param_defs(self) -> dict:
        return transformer_defs(self.cfg)

    # --- steps ---
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        params = cast_params(params, cfg.compute_dtype)
        tokens = batch["tokens"]                      # (B, T)
        B, T = tokens.shape
        x = L.embed_tokens(tokens, params["tok"], cfg)
        positions = batch.get(
            "positions", jnp.broadcast_to(jnp.arange(T)[None], (B, T)))
        h, aux = forward_train(params, x, positions, cfg)
        logits = L.logits_out(h, params["tok"], cfg)
        loss = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
        return loss + 0.01 * aux

    def prefill(self, params, batch):
        cfg = self.cfg
        params = cast_params(params, cfg.compute_dtype)
        tokens = batch["tokens"]
        B, T = tokens.shape
        x = L.embed_tokens(tokens, params["tok"], cfg)
        positions = batch.get(
            "positions", jnp.broadcast_to(jnp.arange(T)[None], (B, T)))
        h, cache = forward_prefill(params, x, positions, cfg)
        logits = L.logits_out(h[:, -1:], params["tok"], cfg)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos, rope_pos=None,
                    counts: bool = False):
        """→ (logits, cache); with ``counts`` (MoE) also the step's routing
        counts summed over layers: (token, expert) rows routed to held
        experts, and held experts hit."""
        cfg = self.cfg
        params = cast_params(params, cfg.compute_dtype)
        x = L.embed_tokens(tokens, params["tok"], cfg)    # (B, 1, d)
        h, *rest = forward_decode(params, x, cache, pos, cfg,
                                  rope_pos=rope_pos, counts=counts)
        logits = L.logits_out(h, params["tok"], cfg)
        return (logits, *rest)

    def init_cache_shape(self, batch: int, max_len: int):
        cfg = self.cfg
        S = min(max_len, cfg.window) if cfg.window else max_len
        shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.d_head)
        return (jax.ShapeDtypeStruct(shape, cfg.compute_dtype),) * 2

    def init_cache(self, batch: int, max_len: int):
        return tuple(
            jnp.zeros(s.shape, s.dtype) for s in self.init_cache_shape(batch, max_len)
        )
