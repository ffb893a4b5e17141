"""Logical-axis sharding rules (DP/FSDP/TP/EP + decode-SP).

Models annotate params/activations with *logical* axes ("embed", "heads",
"batch", …). Rules map logical axes to mesh axes:

  batch   → (pod, data)     data parallelism across pods and the data axis
  embed   → (pod, data)     FSDP (ZeRO-3) weight sharding on the embed dim
  heads / kv / ffn / expert / vocab → model   tensor/expert parallelism
  kv_seq  → model            decode-time KV sequence parallelism (SP) used
                             when kv head sharding is unavailable
  layers / seq / state → None (replicated / unsharded)

GSPMD pads transparently when an axis size is not divisible by the mesh
axis (e.g. 40 heads over model=16) — padding waste shows up honestly in
the roofline's MODEL_FLOPS/HLO_FLOPs ratio.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

_ACTIVE_RULES: dict[str, Any] | None = None


def default_rules(multi_pod: bool = False, **overrides: Any) -> dict[str, Any]:
    fsdp = ("pod", "data") if multi_pod else ("data",)
    rules: dict[str, Any] = {
        "batch": fsdp,
        "embed": fsdp,
        "heads": "model",
        "kv": "model",
        "ffn": "model",
        "expert": "model",
        "vocab": "model",
        "kv_seq": None,
        "kv_dh": None,     # decode-cache head_dim sharding (awkward kv counts)
        "seq": None,
        "layers": None,
        "state": None,
        # Activations: the residual (embed) dim stays unsharded — "embed"
        # means FSDP only for *weights*; shard() translates it.
        "act_embed": None,
    }
    rules.update(overrides)
    return rules


def resolve(axis: str | None):
    if axis is None:
        return None
    if _ACTIVE_RULES is None:
        return None
    return _ACTIVE_RULES.get(axis)


def resolver():
    """Capture the current rules into a resolve callable (for spec_tree)."""
    rules = dict(_ACTIVE_RULES or {})

    def _resolve(axis: str | None):
        if axis is None:
            return None
        return rules.get(axis)

    return _resolve


@contextlib.contextmanager
def use_rules(rules: dict[str, Any] | None):
    global _ACTIVE_RULES
    prev = _ACTIVE_RULES
    _ACTIVE_RULES = rules
    try:
        yield
    finally:
        _ACTIVE_RULES = prev


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Logical with_sharding_constraint; no-op outside a mesh/rules scope.

    Activation-side translation: "embed" (a *weight* FSDP axis) resolves to
    the activation rule "act_embed" (unsharded by default) so batch/embed
    never collide on one tensor.
    """
    if _ACTIVE_RULES is None:
        return x
    axes = tuple("act_embed" if a == "embed" else a for a in axes)
    spec = P(*(resolve(a) for a in axes))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no ambient mesh (single-device smoke tests)
