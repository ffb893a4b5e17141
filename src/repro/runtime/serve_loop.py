"""Serving runtime: batched prefill + greedy decode with KV/state cache.

Online auto-tuning (paper technique, serving workload) is configured by
the embedded :class:`~repro.api.TuningConfig` (``ServeConfig.tuning``)
and owned by a :class:`~repro.api.TuningSession` — the one front door to
the coordinator machinery. The serving regime it runs under:

  * the regeneration budget accrues from **busy time** (kernel-call time
    actually observed), not lifetime wall-clock, so a long-idle server
    cannot burst accrued budget onto one request; the register()-time
    reference measurement is charged to the same budget;
  * sequence lengths are **bucketed to powers of two** (nearest in log
    space), so varied prompt shapes share tuners instead of accumulating
    one tuner (plus pinned evaluation closures) per exact shape;
  * exhausted tuners converge (closures released) and idle tuners are
    evicted by the session lifecycle;
  * the search strategy is pluggable (``TuningConfig.strategy``: any
    name registered in :mod:`repro.core.explorer`);
  * **candidate compilation is off the request path**: variants are
    built by the session's background pipeline (and memoized in its
    process-wide generation cache, so buckets re-registered after
    eviction or a restart warm-start never recompile) while the live
    step-programs keep serving — the paper's double-buffered code
    generation, serving-grade;
  * **one tuning level**: the tuners are the two step programs,
    ``serve_prefill`` (attention tiles ``attn_q_chunk``/``attn_k_chunk``)
    and ``serve_decode`` (``decode_k_chunk``). A point is a
    ``ModelConfig`` the program is built from, so the tiles of a kernel
    lowered inside the program (the prefill's fused attention on a TPU)
    are the point's.

Pass a long-lived session (one per serving process) so tuning state,
budget and warm-started best points persist across requests; within a
single ``generate`` call tuning already begins between decode steps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.api import TuningConfig, TuningSession, serve_tuning_defaults
from repro.configs.base import ModelConfig
from repro.core import (
    Compilette,
    Evaluator,
    Param,
    clamped_options,
    product_space,
    telemetry,
)
from repro.kernels.attention.ops import prefill_kernel_tiles
from repro.models.model import build_model

__all__ = [
    "ServeConfig",
    "generate",
    "serve_tuning_defaults",   # re-export: the regime base lives in api
]

class ServeConfig:
    """Serving knobs; tuning knobs live in the embedded ``tuning`` config."""

    def __init__(
        self,
        max_new_tokens: int = 32,
        greedy: bool = True,
        temperature: float = 1.0,
        seed: int = 0,
        tuning: TuningConfig | None = None,
    ) -> None:
        self.max_new_tokens = max_new_tokens
        self.greedy = greedy
        self.temperature = temperature
        self.seed = seed
        self.tuning = tuning if tuning is not None else \
            serve_tuning_defaults()

    def __repr__(self) -> str:  # cache_token-stable (identity-free)
        return (f"ServeConfig(max_new_tokens={self.max_new_tokens}, "
                f"greedy={self.greedy}, temperature={self.temperature}, "
                f"seed={self.seed}, tuning={self.tuning})")


def _prefill_compilette(model_cfg: ModelConfig, seq: int) -> Compilette:
    """Points are prefill step-programs: attention chunking variants.

    ``seq`` is the (bucketed) sequence extent bounding the chunk options.
    """
    space = product_space([
        Param("attn_q_chunk", clamped_options((32, 64, 128, 256), seq),
              phase=1, switch_rank=0),
        Param("attn_k_chunk", clamped_options((32, 64, 128, 256), seq),
              phase=1, switch_rank=1),
    ])

    def gen(point, **spec):
        cfg2 = dataclasses.replace(
            model_cfg,
            attn_q_chunk=point["attn_q_chunk"],
            attn_k_chunk=point["attn_k_chunk"],
        )
        return jax.jit(build_model(cfg2).prefill)

    # cache_token: compilettes named "serve_prefill" exist per model
    # config; without the token the process-wide GenerationCache could
    # hand one model's compiled step-program to another with the same
    # shape specialization
    return Compilette("serve_prefill", space, gen,
                      cache_token=repr(model_cfg))


def _decode_program(model_cfg: ModelConfig):
    """The served decode step. Its cache (argument 1) is donated, so the
    step updates it in place; the reference and every tuned variant are
    this kind of program. A MoE model's step also returns its routing
    counts (``TransformerLM.decode_step``)."""
    step = build_model(model_cfg).decode_step
    if model_cfg.family == "moe":
        step = functools.partial(step, counts=True)
    return jax.jit(step, donate_argnums=(1,))


def _decode_compilette(model_cfg: ModelConfig, max_len: int) -> Compilette:
    """Points are decode step-programs: flash-decoding KV-chunk variants."""
    space = product_space([
        Param("decode_k_chunk",
              clamped_options((128, 256, 512, 1024, 4096), max_len),
              phase=1),
    ])

    def gen(point, **spec):
        cfg2 = dataclasses.replace(
            model_cfg, decode_k_chunk=point["decode_k_chunk"])
        return _decode_program(cfg2)

    return Compilette("serve_decode", space, gen,
                      cache_token=repr(model_cfg))


def _platform(params: Any) -> str:
    """The platform the parameters, and so the step programs, live on."""
    leaf = jax.tree.leaves(params)[0]
    if isinstance(leaf, jax.Array):
        return next(iter(leaf.devices())).platform
    return jax.default_backend()


def _prefill_tiles(model_cfg: ModelConfig, prefill: Any, params: Any,
                   B: int, T: int) -> tuple[int, int]:
    """``prefill_kernel_tiles`` of one served prefill, at the blocks of
    the served program: the tuner's point, else the reference's."""
    point = getattr(getattr(prefill, "tuner", None), "last_served_point",
                    None)
    if point:
        blocks = point["attn_q_chunk"], point["attn_k_chunk"]
    else:
        blocks = model_cfg.attn_q_chunk, model_cfg.attn_k_chunk
    return prefill_kernel_tiles(model_cfg, _platform(params), B, T, blocks)


@telemetry.traced("serve.request")
def generate(
    model_cfg: ModelConfig,
    batch: dict[str, Any],
    serve: ServeConfig | None = None,
    session: TuningSession | None = None,
) -> dict[str, Any]:
    """Prefill the prompt batch, then decode ``max_new_tokens`` greedily.

    Tuning state lives in ``session`` (one per serving process); without
    one, an ephemeral session is built from ``serve.tuning`` and closed
    when the request finishes.

    Returns the tokens, ``first_token_s`` (entry until the prefill's
    logits are ready), ``prefill_s`` (prefill and cache widening),
    ``decode_s``, and with tuning on the session's stats (``autotune``).
    """
    t_entry = time.perf_counter()
    serve = serve or ServeConfig()
    tuning = serve.tuning.tune_program
    own_session = False
    if tuning and session is None:
        session = TuningSession(serve.tuning)
        own_session = True
    decode_state: dict[str, Any] = {}
    with telemetry.span("serve.setup"):
        model = build_model(model_cfg)
        from repro.models.params import init_tree
        params = batch.pop("params", None)
        if params is None:
            params = init_tree(model.param_defs(),
                               jax.random.PRNGKey(serve.seed),
                               model_cfg.param_dtype)

        B, T = batch["tokens"].shape
        max_len = T + serve.max_new_tokens
        if model_cfg.family == "vlm":
            max_len += model_cfg.vision_patches

        prefill = jax.jit(model.prefill)
        decode = _decode_program(model_cfg)

        if tuning:
            # The compilette's chunk options are bounded by the BUCKETED
            # extent, matching the bucketed specialization key the
            # session registers under — so seq 120 and 150 build the
            # identical 128-bucket space and share one tuner.
            seq_b = session.coordinator.lifecycle.bucket_length(T)
            prefill_ev = Evaluator(
                mode="real", real_runs=1, warmup=1,
                make_args=lambda: (params, batch))
            prefill = session.register(
                "serve_prefill", _prefill_compilette(model_cfg, seq_b),
                prefill_ev,
                specialization={"seq": T, "batch": B},
                reference_fn=prefill,
            )
            # register() is idempotent across requests: point the
            # (possibly pre-existing) evaluator at THIS request's inputs
            # so measurements stay representative of live traffic.
            prefill.tuner.evaluator.make_args = prefill_ev.make_args

    scope_ctx = session.scope() if session is not None \
        else contextlib.nullcontext()
    try:
        with scope_ctx:
            return _generate_inner(
                model_cfg, model, params, batch, serve, session,
                prefill, decode, B, T, max_len, tuning, decode_state,
                t_entry)
    finally:
        if own_session:
            session.close()


def _generate_inner(
    model_cfg, model, params, batch, serve, session,
    prefill, decode, B, T, max_len, tuning, decode_state, t_entry,
) -> dict[str, Any]:
    t0 = time.perf_counter()
    with telemetry.span("serve.prefill"):
        logits, cache = prefill(params, batch)
        jax.block_until_ready(logits)
    t_first = time.perf_counter()
    with telemetry.span("serve.cache_widen"):
        # widen KV caches to max_len where the family uses positional
        # caches
        full = model.init_cache(B, max_len)
        widened = []
        for got, want in zip(cache, full):
            if got.shape == want.shape:
                widened.append(got)
            else:
                pads = [(0, w - g) for g, w in zip(got.shape, want.shape)]
                widened.append(jnp.pad(got, pads))
        cache = tuple(widened)
        # dispatch is asynchronous: without the sync this would time the
        # enqueue, not the device's prefill
        jax.block_until_ready((logits, cache))
    t_prefill = time.perf_counter() - t0
    # one device-side flag over every step's logits, read once at the end
    finite = jnp.isfinite(logits).all()

    tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tokens]
    pos0 = T if model_cfg.family != "vlm" else T + model_cfg.vision_patches
    tiles, tiles_run = _prefill_tiles(model_cfg, prefill, params, B, pos0)
    telemetry.counter("attn.prefill_tiles", tiles)
    telemetry.counter("attn.prefill_tiles_run", tiles_run)

    with telemetry.span("serve.setup"):
        if tuning:
            # The decode evaluator replays the *current* decoding state
            # on a copy of its cache, one per call (the step consumes the
            # cache it is given); its outputs are discarded, so
            # measurement is side-effect-free.
            decode_state.update(cache=cache, tokens=tokens,
                                pos=jnp.int32(pos0))
            max_len_b = session.coordinator.lifecycle.bucket_length(max_len)
            decode_ev = Evaluator(
                mode="real", real_runs=1, warmup=1, fresh_args=True,
                make_args=lambda: (params,
                                   jax.device_put(decode_state["cache"],
                                                  may_alias=False),
                                   decode_state["tokens"],
                                   decode_state["pos"]))
            decode = session.register(
                "serve_decode", _decode_compilette(model_cfg, max_len_b),
                decode_ev,
                specialization={"max_len": max_len, "batch": B},
                reference_fn=decode,
            )
            decode.tuner.evaluator.make_args = decode_ev.make_args

    moe_counts = None     # MoE routing counts, summed on the device
    t1 = time.perf_counter()
    with telemetry.span("serve.decode"):
        for i in range(serve.max_new_tokens - 1):
            with telemetry.span("serve.decode_step"):
                logits, cache, *counts = decode(params, cache, tokens,
                                                jnp.int32(pos0 + i))
                if counts:
                    moe_counts = counts[0] if moe_counts is None \
                        else moe_counts + counts[0]
                finite &= jnp.isfinite(logits).all()
                tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(
                    jnp.int32)
                out_tokens.append(tokens)
            if tuning:
                decode_state.update(cache=cache, tokens=tokens,
                                    pos=jnp.int32(pos0 + i + 1))
                session.maybe_pump()
        jax.block_until_ready(tokens)
    t_decode = time.perf_counter() - t1

    with telemetry.span("serve.finish"):
        generated = jnp.concatenate(out_tokens, axis=1)
        if moe_counts is not None:
            rows, active = moe_counts.tolist()
            telemetry.counter("moe.rows", rows)
            telemetry.counter("moe.active", active)
        n_new = generated.shape[1]
        out = {
            "tokens": generated,
            "first_token_s": t_first - t_entry,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            # the first new token comes from prefill; the loop decodes
            # the rest
            "decode_tokens_per_s": (
                B * (n_new - 1) / t_decode if t_decode > 0 else 0.0),
            "logits_finite": bool(finite),
        }
        if tuning:
            session.save()
            # Lifecycle pass at request end: converged tuners release the
            # evaluator closures pinning this request's params/batch/
            # cache, and tuners idle past the eviction horizon are
            # unregistered.
            session.sweep()
            out["autotune"] = session.stats()
        return out
