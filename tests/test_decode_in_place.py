"""The served decode step updates its KV cache in place.

The cache rides the layer scan's carry as it is (no bit views of the
whole stacked cache) and the step donates it, so the compiled program
aliases the cache argument with its output and makes no whole-cache
copy. Tuning measures decode variants on copies of the live cache, one
per timed call, so a donated measurement never deletes the cache that
serving uses next.
"""

from __future__ import annotations

import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.core import Evaluator, VirtualClock
from repro.core import evaluator as evaluator_mod


def _bf16_dense():
    return dataclasses.replace(REGISTRY["deepseek-7b"].reduced(),
                               param_dtype=jnp.bfloat16,
                               compute_dtype=jnp.bfloat16)


def _program(which, cfg, max_len):
    """The decode program ``generate`` serves, or a tuned variant's."""
    from repro.runtime.serve_loop import _decode_compilette, _decode_program

    if which == "generate":
        return _decode_program(cfg)
    comp = _decode_compilette(cfg, max_len)
    return comp.generate(next(comp.space.iter_valid())).fn


# ------------------------------------------------------ the compiled program
@pytest.mark.parametrize("which", ["generate", "variant"])
def test_decode_program_aliases_its_cache_and_copies_none_of_it(which):
    from repro.models.model import build_model
    from repro.models.params import init_tree

    cfg = _bf16_dense()
    B, S = 1, 200
    model = build_model(cfg)
    params = jax.eval_shape(lambda: init_tree(
        model.param_defs(), jax.random.PRNGKey(0), cfg.param_dtype))
    cache = model.init_cache_shape(B, S)
    cache_bytes = sum(c.size * c.dtype.itemsize for c in cache)
    compiled = _program(which, cfg, S).lower(
        params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    whole = "[" + ",".join(map(str, cache[0].shape)) + "]"
    copies = [line.strip() for line in compiled.as_text().splitlines()
              if re.search(r"=\s*\w+" + re.escape(whole)
                           + r"\S*\s+(copy|bitcast-convert)\(", line)]
    assert not copies, copies


# ---------------------------------------------------- one input per timed call
def _virtual_time(monkeypatch, clock):
    monkeypatch.setattr(evaluator_mod, "time",
                        types.SimpleNamespace(perf_counter=clock))


@pytest.mark.parametrize("mode,calls", [("real", 1 + 3),
                                        ("training", 1 + 2 * 3)])
def test_fresh_args_makes_each_call_its_own_inputs_outside_the_interval(
        monkeypatch, mode, calls):
    clock = VirtualClock()
    _virtual_time(monkeypatch, clock)
    made, seen = [], []

    def make_args():
        clock.advance(1.0)              # the factory's work: a cache copy
        made.append(object())
        return (made[-1],)

    def fn(x):
        assert x not in seen, "an input was handed to two calls"
        seen.append(x)
        clock.advance(0.25)
        return x

    ev = Evaluator(mode=mode, real_runs=3, groups=2, group_size=3, warmup=1,
                   make_args=make_args, fresh_args=True)
    m = ev.evaluate(fn)
    assert len(made) == calls == m.n_runs
    assert seen == made
    assert m.score_s == 0.25
    # the budget is charged for the whole evaluation, copies included
    assert m.eval_time_s == pytest.approx(calls * 0.25 + (calls - 1) * 1.0)


def test_without_fresh_args_every_call_shares_one_input(monkeypatch):
    clock = VirtualClock()
    _virtual_time(monkeypatch, clock)
    made = []
    ev = Evaluator(mode="real", real_runs=3, warmup=1,
                   make_args=lambda: made.append(1) or (1,))
    assert ev.evaluate(lambda x: clock.advance(0.5)).score_s == 0.5
    assert made == [1]


# ------------------------------------------------------- serving with tuning
def test_decode_evaluations_between_steps_keep_the_served_tokens(monkeypatch):
    """Program tuning with an open budget pumps after every decode step,
    so decode variants are measured between steps on copies of the live
    cache. Nothing reads a donated buffer, and the tokens are those of a
    run with tuning off (the variants' cache chunks divide no served
    cache length here, so they all compute the same)."""
    from repro.api import TuningSession
    from repro.runtime.serve_loop import ServeConfig, generate

    cfg = _bf16_dense()
    T, new = 200, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, cfg.vocab)
    plain = generate(cfg, {"tokens": tokens},
                     ServeConfig(max_new_tokens=new, seed=1))
    serve = ServeConfig(max_new_tokens=new, seed=1, autotune=True,
                        tune_max_overhead=1e6, pump_every=1,
                        async_generation=False, kernel_tuning="program",
                        idle_evict_s=None)
    session = TuningSession(serve.tuning)
    timed = []
    evaluate = Evaluator.evaluate

    def counting(self, fn, args=None):
        m = evaluate(self, fn, args)
        if self.fresh_args:
            timed.append(m.n_runs)
        return m

    monkeypatch.setattr(Evaluator, "evaluate", counting)
    try:
        tuned = generate(cfg, {"tokens": tokens}, serve, session=session)
    finally:
        session.close()
    (decode_key,) = [k for k in tuned["autotune"]["kernels"]
                     if "serve_decode" in k]
    decode_stats = tuned["autotune"]["kernels"][decode_key]
    # the reference measurement and at least one variant's, between steps
    assert len(timed) >= 2 and decode_stats["n_explored"] >= 1
    assert tuned["logits_finite"]
    assert tuned["tokens"].tolist() == plain["tokens"].tolist()
