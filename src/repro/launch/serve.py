"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        [--autotune --requests 4 --registry /tmp/serve_tuned.json]

All tuning knobs are the canonical ``repro.tune`` flag set, declared once
by :meth:`repro.TuningConfig.add_flags` (strategy, kernel granularity and
per-kernel strategies, budget caps, SLO gate, bucketing, async pipeline);
the CLI builds one :class:`repro.TuningSession` and every request rides
it, so later requests reuse the variants earlier ones discovered (and
``--registry`` persists them across restarts).

``--kernel-tuning`` selects the tuning granularity: ``program`` (whole
step-programs), ``kernel`` (the model's matmul / attention / rmsnorm /
decode_attention Pallas kernels tune as independent session-managed
compilettes), ``both`` (hierarchical: step-programs plus their
constituent kernels under one shared budget) or ``off``.
"""

import argparse
from typing import Any, Iterator


def request_batch(cfg: Any, req: int, batch: int,
                  prompt_len: int) -> dict[str, Any]:
    """Request ``req``'s inputs: random prompt tokens seeded by its index,
    plus the stub audio/vision inputs the encdec and vlm families take."""
    import jax

    out = {"tokens": jax.random.randint(
        jax.random.PRNGKey(req), (batch, prompt_len), 0, cfg.vocab)}
    if cfg.family == "encdec":
        out["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(1),
            (batch, cfg.enc_frames, cfg.d_model)) * 0.05
    if cfg.family == "vlm":
        out["vision"] = jax.random.normal(
            jax.random.PRNGKey(1), (batch, 16, cfg.d_model)) * 0.05
    return out


def serve_requests(cfg: Any, serve: Any, session: Any, *, batch: int,
                   prompt_len: int, requests: int,
                   params: Any | None = None) -> Iterator[dict[str, Any]]:
    """Serve ``requests`` requests through one session, yielding each
    request's :func:`~repro.runtime.serve_loop.generate` output.

    ``params`` (random weights built by the caller) are shared by every
    request; without them each request draws its own from the serve seed.
    """
    from repro.runtime.serve_loop import generate

    for req in range(requests):
        inputs = request_batch(cfg, req, batch, prompt_len)
        if params is not None:
            inputs["params"] = params
        yield generate(cfg, inputs, serve, session=session)


def main() -> None:
    # repro.api is jax-free: --help and flag errors stay fast; the
    # jax-heavy loop modules load only after parsing succeeds
    from repro.api import (
        TuningConfig, TuningSession, serve_tuning_defaults)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=1)
    # the canonical tuning flag set, declared once; the serving regime
    # (busy-time budget, charged init, 5% cap) seeds the flag defaults
    base = serve_tuning_defaults()
    TuningConfig.add_flags(ap, base=base)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config
    from repro.runtime.serve_loop import ServeConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TuningConfig.from_flags(args, base=base)
    serve = ServeConfig(max_new_tokens=args.tokens, tuning=tcfg)
    # kernel_tuning="off" disables tuning even with --autotune: no
    # session, and generate() emits no "autotune" stats block
    session = TuningSession(tcfg) if tcfg.active else None

    for req, out in enumerate(serve_requests(
            cfg, serve, session, batch=args.batch,
            prompt_len=args.prompt_len, requests=args.requests)):
        line = (f"req {req}: {out['decode_tokens_per_s']:.1f} tok/s, "
                f"prefill {out['prefill_s']*1e3:.0f} ms")
        if session is not None:
            a = out["autotune"]
            lc = a["lifecycle"]
            gc = a["generation_cache"]
            line += (f"  [tuning({args.strategy}/{args.kernel_tuning}): "
                     f"{a['regenerations']} regens, {a['swaps']} swaps, "
                     f"overhead {a['overhead_frac']*100:.1f}%, "
                     f"gen stall {a['gen_stall_s']*1e3:.0f} ms, "
                     f"cache {gc['hit_rate']*100:.0f}% hit, "
                     f"tuners {a['n_kernels']} "
                     f"({lc['converged']} converged, "
                     f"{lc['retired']} retired)]")
            if args.kernel_tuning in ("kernel", "both"):
                per = ", ".join(
                    f"{name}:{k['strategy']}×{k['regenerations']}"
                    for name, k in sorted(a["kernels"].items())
                    if k.get("plane_managed"))
                line += f"\n        kernels: {per}"
        print(line)
    if session is not None:
        session.close()


if __name__ == "__main__":
    main()
