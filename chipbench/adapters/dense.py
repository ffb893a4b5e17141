"""The program's model configuration for a dense decoder's config file.

Starts from the program's own entry for ``program_arch`` and sets the
depth and the served dtype from the file. Every width the file states
has to match the program's entry, or the run stops: a width is never
changed here.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(cfg: dict[str, Any], base: Any = None) -> Any:
    """``base`` stands in for the program's entry (a reduced one in the
    CPU tests)."""
    from repro.configs import get_config

    if base is None:
        base = get_config(cfg["program_arch"])
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    want = {
        "family": "dense", "d_model": d, "n_heads": H,
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_head": cfg.get("head_dim") or d // H,
        "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]), "act": "swiglu",
        "norm": "rmsnorm", "qkv_bias": False, "window": None,
        "parallel_block": False, "logit_softcap": None, "use_rope": True,
    }
    wrong = {k: (getattr(base, k), v) for k, v in want.items()
             if getattr(base, k) != v}
    if cfg["hidden_act"] != "silu" or cfg["rms_norm_eps"] != 1e-6:
        wrong["hidden_act/rms_norm_eps"] = (
            ("silu", 1e-6), (cfg["hidden_act"], cfg["rms_norm_eps"]))
    if cfg.get("rope_scaling"):
        wrong["rope_scaling"] = (None, cfg["rope_scaling"])
    if wrong:
        raise ValueError(f"{cfg['program_arch']}: the program's entry differs "
                         f"from the config file (program, file): {wrong}")
    dt = DTYPES[cfg["dtype"]]
    return dataclasses.replace(base, n_layers=cfg["num_hidden_layers"],
                               param_dtype=dt, compute_dtype=dt)
