"""Training runtime: fault-tolerant loop with integrated online auto-tuning.

Framework integration of the paper's technique: during early steps the
online auto-tuner explores *step-program variants* (attention chunk sizes
— the vectLen/unroll analogues of the compiled train step) under the
regeneration-budget policy, hot-swapping the active jitted step when a
variant measures faster. All overheads are part of the wall-clock the loop
reports, exactly like the paper's "all run-time overheads included".

Tuning is configured by the embedded :class:`~repro.api.TuningConfig`
(``TrainLoopConfig.tuning``) and owned by a
:class:`~repro.api.TuningSession`: the budget is shared with any other
tunable step-programs, and the best points are persisted next to the
checkpoints so a restarted (or elastically re-scaled) job warm-starts
instead of re-exploring.

Fault tolerance:
  * checkpoint every ``ckpt_every`` steps (atomic, retained set),
  * auto-resume from the latest checkpoint (data stream is a pure function
    of the step index, so restarts are bit-deterministic),
  * optional injected failure (tests preemption recovery),
  * straggler watchdog: steps slower than ``straggler_factor`` × running
    median are flagged (the single-host analogue of replacing a slow
    worker; the count is reported).
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.api import TuningConfig, TuningSession, train_tuning_defaults
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, ShapeSpec
from repro.core import (
    Compilette, Evaluator, Param, clamped_options, product_space,
)
from repro.data.pipeline import batches_for, device_put_batch
from repro.distributed.compression import ErrorFeedback
from repro.models.model import build_model
from repro.models.params import init_tree
from repro.optim.adamw import AdamW, OptimizerConfig

class TrainLoopConfig:
    """Loop knobs; tuning knobs live in the embedded ``tuning`` config."""

    def __init__(
        self,
        steps: int = 50,
        ckpt_every: int = 20,
        ckpt_dir: str = "/tmp/repro_ckpt",
        keep: int = 3,
        seed: int = 0,
        compress_grads: bool = False,
        straggler_factor: float = 3.0,
        fail_at_step: int | None = None,
        log_every: int = 10,
        tuning: TuningConfig | None = None,
    ) -> None:
        self.steps = steps
        self.ckpt_every = ckpt_every
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self.seed = seed
        self.compress_grads = compress_grads
        self.straggler_factor = straggler_factor
        self.fail_at_step = fail_at_step
        self.log_every = log_every
        self.tuning = tuning if tuning is not None else \
            train_tuning_defaults()


class FaultInjected(RuntimeError):
    pass


def _make_step(model, optimizer, ef: ErrorFeedback | None, cfg: ModelConfig):
    def step(params, opt_state, ef_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        if ef is not None:
            grads, ef_state = ef.apply(grads, ef_state)
        params, opt_state, gnorm = optimizer.update(grads, opt_state, params)
        return loss, params, opt_state, ef_state, gnorm
    return step


def _attention_step_compilette(model_cfg: ModelConfig, model, optimizer,
                               ef, sample_batch, seq: int) -> Compilette:
    """Compilette whose points are attention-chunk program variants.

    Chunk options are bounded by the training sequence length up front
    (same dedup as the serve compilettes): chunks past ``seq`` all
    compile to the same program, so enumerating them would waste the
    shared regeneration budget.
    """
    space = product_space([
        Param("attn_q_chunk", clamped_options((64, 128, 256), seq),
              phase=1, switch_rank=0),
        Param("attn_k_chunk", clamped_options((64, 128, 256, 512), seq),
              phase=1, switch_rank=1),
    ])

    def generate(point, **spec):
        cfg2 = dataclasses.replace(
            model_cfg,
            attn_q_chunk=point["attn_q_chunk"],
            attn_k_chunk=point["attn_k_chunk"],
        )
        model2 = build_model(cfg2)
        raw = _make_step(model2, optimizer, ef, cfg2)
        return jax.jit(raw, donate_argnums=())

    return Compilette("train_step_attn", space, generate,
                      cache_token=repr(model_cfg))


def train(
    model_cfg: ModelConfig,
    shape: ShapeSpec,
    loop: TrainLoopConfig | None = None,
    opt_cfg: OptimizerConfig | None = None,
) -> dict[str, Any]:
    loop = loop or TrainLoopConfig()
    tcfg = loop.tuning
    model = build_model(model_cfg)
    optimizer = AdamW(opt_cfg or OptimizerConfig(warmup_steps=10,
                                                 total_steps=loop.steps))
    ef = ErrorFeedback() if loop.compress_grads else None
    ckpt = Checkpointer(loop.ckpt_dir, keep=loop.keep)
    registry_path = f"{loop.ckpt_dir}/tuned.json"

    # ---- init or resume -------------------------------------------------
    key = jax.random.PRNGKey(loop.seed)
    params = init_tree(model.param_defs(), key, model_cfg.param_dtype)
    opt_state = optimizer.init(params)
    ef_state = ef.init(params) if ef else None
    start_step = 0
    latest = ckpt.latest_step()
    if latest is not None:
        skeleton = {"params": params, "opt": opt_state}
        state, manifest = ckpt.restore(skeleton, latest)
        params, opt_state = state["params"], state["opt"]
        params = jax.tree.map(jnp.asarray, params)
        opt_state = jax.tree.map(jnp.asarray, opt_state)
        start_step = manifest["step"]

    # ---- step program (with optional online auto-tuning) ---------------
    stream = batches_for(model_cfg, shape, seed=loop.seed + 1,
                         start_step=start_step)
    first_batch = device_put_batch(next(stream))
    raw_step = jax.jit(_make_step(model, optimizer, ef, model_cfg))

    session = None
    tuner = None
    if tcfg.tune_program:
        # One session per training process: a single regeneration budget
        # shared by every tunable step-program, warm-started from the
        # checkpoint-adjacent registry so a restarted job skips
        # re-exploration. Variant jitting overlaps the training steps; a
        # resumed job whose registry warm-start re-proposes known points
        # hits the generation cache instead of re-building the step
        # program.
        if tcfg.registry_path is None:
            tcfg = dataclasses.replace(tcfg, registry_path=registry_path)
        session = TuningSession(tcfg)
        comp = _attention_step_compilette(
            model_cfg, model, optimizer, ef, first_batch, shape.seq_len)
        spec = {"seq": shape.seq_len}
        evaluator = Evaluator(
            mode="real", real_runs=2, warmup=1,
            make_args=lambda: (params, opt_state, ef_state, first_batch))
        tuner = session.register(
            "train_step_attn", comp, evaluator,
            specialization=spec, reference_fn=raw_step,
        )

    # ---- loop ------------------------------------------------------------
    losses: list[float] = []
    durations: list[float] = []
    stragglers = 0
    t_start = time.perf_counter()
    step = start_step
    batch = first_batch
    scope_ctx = session.scope() if session is not None \
        else contextlib.nullcontext()
    with scope_ctx:
        while step < loop.steps:
            if loop.fail_at_step is not None and step == loop.fail_at_step:
                raise FaultInjected(f"injected failure at step {step}")
            t0 = time.perf_counter()
            fn = tuner if tuner is not None else raw_step
            loss, params, opt_state, ef_state, gnorm = fn(
                params, opt_state, ef_state, batch)
            loss = float(loss)
            if session is not None:
                session.maybe_pump()
            dt = time.perf_counter() - t0
            durations.append(dt)
            if len(durations) >= 5:
                med = statistics.median(durations)
                if dt > loop.straggler_factor * med:
                    stragglers += 1
            losses.append(loss)
            step += 1
            if step % loop.ckpt_every == 0 or step == loop.steps:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          extra={"loss": loss})
                if session is not None:
                    session.save()
            batch = device_put_batch(next(stream))

    wall = time.perf_counter() - t_start
    out = {
        "steps": step,
        "start_step": start_step,
        "final_loss": losses[-1] if losses else None,
        "first_loss": losses[0] if losses else None,
        "wall_s": wall,
        "stragglers_flagged": stragglers,
        "losses": losses,
    }
    if tuner is not None:
        out["autotune"] = tuner.stats()
    if session is not None:
        session.close()
        out["coordinator"] = session.stats()
    return out
