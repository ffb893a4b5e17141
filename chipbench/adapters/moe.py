"""The program's model configuration for a Qwen3-MoE config file.

Starts from the program's own entry for ``program_arch`` and sets the
depth, the experts this chip holds (``num_experts`` of them from
``expert_share.first_expert`` on) and the served dtype from the file.
Every width the file states, the router's included, has to match the
program's entry, or the run stops: a width is never changed here.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from chipbench.adapters.dense import DTYPES


def program_config(cfg: dict[str, Any], base: Any = None) -> Any:
    """``base`` stands in for the program's entry (a reduced one in the
    CPU tests)."""
    from repro.configs import get_config

    if base is None:
        base = get_config(cfg["program_arch"])
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    share = cfg["expert_share"]
    want = {
        "family": "moe", "d_model": d, "n_heads": H,
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_head": cfg.get("head_dim") or d // H,
        "d_ff": cfg["moe_intermediate_size"], "vocab": cfg["vocab_size"],
        "n_experts": share["router_experts"],
        "top_k": cfg["num_experts_per_tok"], "n_shared_experts": 0,
        "qk_norm": True, "rope_theta": float(cfg["rope_theta"]),
        "act": "swiglu", "norm": "rmsnorm",
        "qkv_bias": cfg["attention_bias"], "window": None,
        "parallel_block": False, "logit_softcap": None, "use_rope": True,
    }
    # an entry that lacks a field (an older program) differs from the file
    wrong = {k: (getattr(base, k, "(absent)"), v) for k, v in want.items()
             if getattr(base, k, "(absent)") != v}
    published = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
                 "norm_topk_prob": True, "decoder_sparse_step": 1,
                 "mlp_only_layers": [], "rope_scaling": None,
                 "tie_word_embeddings": False, "use_sliding_window": False}
    wrong.update({k: (v, cfg.get(k)) for k, v in published.items()
                  if cfg.get(k) != v})
    first, held = share["first_expert"], cfg["num_experts"]
    if not 0 <= first < first + held <= share["router_experts"]:
        wrong["expert_share"] = (f"within {share['router_experts']}",
                                 (first, held))
    if wrong:
        raise ValueError(f"{cfg['program_arch']}: the program's entry differs "
                         f"from the config file (program, file): {wrong}")
    dt = DTYPES[cfg["dtype"]]
    return dataclasses.replace(base, n_layers=cfg["num_hidden_layers"],
                               experts_held=held, first_expert=first,
                               param_dtype=dt, compute_dtype=dt)
