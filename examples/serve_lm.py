"""Serving example: batched prefill + greedy decode with a KV cache, for
any assigned architecture (reduced config so it runs on CPU).

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-1.6b --tokens 32

With ``--autotune`` the request streams tokens while one
:class:`repro.TuningSession` tunes the prefill and decode step-programs
online; ``--requests N`` sends N requests through the same session so
tuning pays off across requests (warm variants, no re-exploration). The
tuning flags are the canonical ``repro.tune`` set declared by
``repro.TuningConfig.add_flags``.
"""

import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax

from repro.api import TuningConfig, TuningSession
from repro.configs import REGISTRY
from repro.runtime.serve_loop import (
    ServeConfig, generate, serve_tuning_defaults)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=sorted(REGISTRY))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=1)
    # demo-friendly base: a generous overhead cap for short runs
    base = dataclasses.replace(serve_tuning_defaults(), max_overhead=0.2)
    TuningConfig.add_flags(ap, base=base)
    args = ap.parse_args()

    tcfg = TuningConfig.from_flags(args, base=base)
    cfg = REGISTRY[args.arch].reduced()
    serve = ServeConfig(max_new_tokens=args.tokens, tuning=tcfg)
    session = TuningSession(tcfg) if tcfg.tune_program else None

    for req in range(args.requests):
        batch = {
            "tokens": jax.random.randint(
                jax.random.PRNGKey(req), (args.batch, args.prompt_len),
                0, cfg.vocab)
        }
        if cfg.family == "encdec":
            batch["audio_embeds"] = jax.random.normal(
                jax.random.PRNGKey(1),
                (args.batch, cfg.enc_frames, cfg.d_model)) * 0.05
        if cfg.family == "vlm":
            batch["vision"] = jax.random.normal(
                jax.random.PRNGKey(1), (args.batch, 16, cfg.d_model)) * 0.05

        t0 = time.perf_counter()
        out = generate(cfg, batch, serve, session=session)
        print(f"req {req}  arch={args.arch} (reduced)  batch={args.batch}")
        print(f"  prefill {out['prefill_s']*1e3:.0f} ms   "
              f"decode {out['decode_s']*1e3:.0f} ms   "
              f"{out['decode_tokens_per_s']:.1f} tok/s   "
              f"total {time.perf_counter()-t0:.1f}s")
        if session is not None:
            a = out["autotune"]
            lc = a["lifecycle"]
            print(f"  tuning[{args.strategy}]: "
                  f"{a['regenerations']} regens {a['swaps']} swaps "
                  f"overhead {a['overhead_frac']*100:.1f}% "
                  f"(budget {a['budget_s']*1e3:.0f} ms, "
                  f"init {a['init_spent_s']*1e3:.0f} ms) "
                  f"tuners {a['n_kernels']} "
                  f"({lc['converged']} converged {lc['retired']} retired)")
    if session is not None:
        session.close()
    if args.requests > 0:
        print("first sequence:", out["tokens"][0].tolist())


if __name__ == "__main__":
    main()
