"""Serve deepseek-7b at its published width on one TPU chip.

    python chip_smoke.py

The quickest proof that the system runs on the chip. It builds
deepseek-7b at full width (d_model 4096, 32 heads of 128 with 32 KV
heads, d_ff 11008, vocab 102400) with bf16 weights drawn from a seed,
and serves 3 requests (batch 2, 128-token prompts, 16 new tokens)
through one ``TuningSession`` with ``kernel_tuning="both"`` and
``gate_mode="check"``: the path ``python -m repro.launch.serve`` takes.
So the jitted step programs, the kernel plane (matmul, attention,
rmsnorm, decode_attention) and the variant gate all run on the chip.

It exits non-zero, without the result line, when JAX finds no TPU, when
any logits are non-finite, when the gate rejects a kernel's base point
against the kernel's ``ref.py``, or when a Pallas kernel variant was
compiled without a Mosaic kernel in its HLO (``tpu_custom_call``), that
is, in interpret mode. The last line of standard output is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time
from typing import Any, Callable

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "deepseek-7b"
BATCH, PROMPT_LEN, NEW_TOKENS, REQUESTS = 2, 128, 16, 3
# All 30 layers: 12.9 GiB of bf16 weights. Compiled for a described v5e,
# prefill and decode leave more than the 1.5 GiB the run needs beside
# the weights out of the chip's 15.75 GiB, so depth is not cut.
N_LAYERS = 30
# decode_attention is a jnp scan: the only catalog kernel without Pallas
PALLAS_KERNELS = ("attention", "matmul", "rmsnorm")


def smoke_config() -> Any:
    """deepseek-7b at its published widths, bf16 weights and compute."""
    import jax.numpy as jnp

    from repro.configs import get_config

    return dataclasses.replace(
        get_config(ARCH), param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16, n_layers=N_LAYERS)


def _record_variants(variants: list) -> Callable[[Any], None]:
    """Compilette hook: note whether each compiled kernel variant holds a
    Mosaic kernel (``tpu_custom_call``) in its HLO."""
    def hook(comp: Any) -> None:
        build = comp._generate

        def recording(point: Any, **sp: Any) -> Any:
            fn = build(point, **sp)
            variants.append((comp.name, dict(point),
                             "tpu_custom_call" in fn.as_text()))
            return fn

        comp._generate = recording
    return hook


def run_smoke(cfg: Any, *, batch: int, prompt_len: int, new_tokens: int,
              requests: int, seed: int = 0,
              log: Callable[[str], None] = print) -> dict[str, Any]:
    """Serve ``cfg`` through one tuning session; return what the checks
    read: per-request outputs, session stats, every compiled kernel
    variant, and the gate's verdict on each kernel's base point."""
    import jax

    from repro.api import TuningSession, serve_tuning_defaults
    from repro.core.gate import VariantGate
    from repro.launch.serve import serve_requests
    from repro.models.model import build_model
    from repro.models.params import init_tree
    from repro.runtime.serve_loop import ServeConfig

    tcfg = dataclasses.replace(
        serve_tuning_defaults(), enabled=True, kernel_tuning="both",
        gate_mode="check")
    serve = ServeConfig(max_new_tokens=new_tokens, seed=seed, tuning=tcfg)
    # drawn in one program: no float32 copy of a bf16 weight is ever live
    init = jax.jit(functools.partial(
        init_tree, build_model(cfg).param_defs(), dtype=cfg.param_dtype))
    t0 = time.perf_counter()
    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"params: {param_bytes} bytes ({param_bytes / 2**30} GiB), "
        f"drawn in {time.perf_counter() - t0} s")

    variants: list = []
    session = TuningSession(tcfg, compilette_hook=_record_variants(variants))
    outs = []
    try:
        for out in serve_requests(cfg, serve, session, batch=batch,
                                  prompt_len=prompt_len, requests=requests,
                                  params=params):
            outs.append(out)
        stats = session.stats()
        base_gate = []
        for handle in session.plane.handles():
            comp = handle.tuner.compilette
            base = handle.tuner.explorer.base_point
            fn = comp.generate(base, **handle.specialization).fn
            ok, reason = VariantGate(comp).check(base, fn)
            base_gate.append((handle.name, base, ok, reason))
    finally:
        session.close()
    return {"outs": outs, "stats": stats, "variants": variants,
            "base_gate": base_gate, "param_bytes": param_bytes}


def failures(report: dict[str, Any], *, batch: int,
             new_tokens: int) -> list[str]:
    """What the run got wrong; empty when the chip run is correct."""
    bad = []
    for i, out in enumerate(report["outs"]):
        if not out["logits_finite"]:
            bad.append(f"request {i}: non-finite logits")
        if tuple(out["tokens"].shape) != (batch, new_tokens):
            bad.append(f"request {i}: tokens of shape {out['tokens'].shape}")
    for name, base, ok, reason in report["base_gate"]:
        if not ok:
            bad.append(f"gate rejected {name}'s base point {base}: {reason}")
    for name, point, has_kernel in report["variants"]:
        if name in PALLAS_KERNELS and not has_kernel:
            bad.append(f"{name} variant {point} compiled without "
                       "tpu_custom_call (interpret mode)")
    kernels = report["stats"]["kernels"]
    for name in PALLAS_KERNELS + ("decode_attention",):
        if not any(k == name or k.startswith(name + "@") for k in kernels):
            bad.append(f"kernel {name} was not attached")
    for key, k in kernels.items():
        if k.get("plane_managed") and k["aot_compiles"] < 1:
            bad.append(f"kernel {key} compiled nothing ahead of time")
    return bad


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    chip = f"{dev.device_kind} ({dev.platform})"
    cfg = smoke_config()
    print(f"compile cache: {cache_dir}")
    print(f"config: {cfg.name} d_model {cfg.d_model}, {cfg.n_heads} heads "
          f"of {cfg.d_head} (kv {cfg.n_kv_heads}), d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, {cfg.n_layers} layers (all; no depth cut), "
          f"bf16 params and compute")
    t0 = time.perf_counter()
    report = run_smoke(cfg, batch=BATCH, prompt_len=PROMPT_LEN,
                       new_tokens=NEW_TOKENS, requests=REQUESTS)
    for i, out in enumerate(report["outs"]):
        print(f"request {i}: prefill_s {out['prefill_s']}, decode "
              f"{out['decode_tokens_per_s']} tokens/s, measured on {chip}")
    s = report["stats"]
    print(f"tuning: {s['regenerations']} regenerations, {s['swaps']} swaps, "
          f"{s['generation']['failed']} generation failures, "
          f"{s['quarantined']} quarantined, gate "
          f"{s['gate_checks'] - s['gate_failures']} passed / "
          f"{s['gate_failures']} failed")
    for key, k in sorted(s["kernels"].items()):
        if k.get("plane_managed"):
            print(f"  kernel {key}: {k['aot_compiles']} AOT compiles, "
                  f"{k['regenerations']} regenerations")
    for name, base, ok, reason in report["base_gate"]:
        print(f"  base-point gate {name} {base}: "
              f"{'pass' if ok else 'FAIL ' + reason}")
    n_pallas = sum(1 for v in report["variants"] if v[0] in PALLAS_KERNELS)
    print(f"compiled kernel variants: {len(report['variants'])} "
          f"({n_pallas} Pallas, each checked for tpu_custom_call)")
    print(f"wall: {time.perf_counter() - t0} s after start-up")
    bad = failures(report, batch=BATCH, new_tokens=NEW_TOKENS)
    for line in bad:
        print(f"chip_smoke: FAIL {line}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
