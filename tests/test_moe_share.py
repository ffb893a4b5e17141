"""The MoE layer as one device's share of an expert-parallel layer, and the
serving path that runs it, against the plain reference
(``chipbench/reference/moe.py``) on seeded weights at a CPU size.

Program and reference both run in float32 here. They sum in different
orders (rows sorted by expert against a loop over experts, chunked
against blocked attention), which moves logits of size ~3 by ~1e-6, so
logits are compared at 1e-4. A row dropped, doubled or given to the
wrong expert moves them by 1e-2 or more, as does a QK-norm scale left
out (``test_qk_norm_scales_change_logits_and_reference_follows``).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spans, weights  # noqa: E402
from chipbench.adapters import moe as adapter  # noqa: E402
from chipbench.reference import moe as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import telemetry  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.moe import moe_ffn, route  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "qwen3-moe-30b-a3b-ep8.json")
ATOL = 1e-4


def small(held=4, first=4, router=16, k=4, layers=2):
    """The cell's config file cut to CPU size, and the program's config
    for it through the adapter (float32 on both sides)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32,
               num_hidden_layers=layers, vocab_size=256, num_experts=held,
               num_experts_per_tok=k, dtype="float32",
               expert_share=dict(cfg["expert_share"], router_experts=router,
                                 first_expert=first))
    base = get_config("qwen3-moe-30b-a3b").reduced(
        d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, vocab=256,
        n_experts=router, top_k=k)
    return cfg, adapter.program_config(cfg, base=base)


def tokens(B, T, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (B, T)).astype(
        np.int32)


def prefill_last(mc, params, toks):
    logits, _ = jax.jit(build_model(mc).prefill)(
        params, {"tokens": jnp.asarray(toks)})
    return np.asarray(logits[:, -1])


@pytest.mark.parametrize("held,first", [(16, 0), (4, 4), (1, 7)])
def test_prefill_logits_match_reference(held, first, monkeypatch):
    """Every expert held, a share of four, and a share of one expert (a
    token then has at most one held choice of its four)."""
    monkeypatch.setattr(moe, "BLOCK_TOKENS", 16)
    cfg, mc = small(held=held, first=first)
    params = weights.draw(ref.param_specs(cfg), 11, jnp.float32)
    toks = tokens(3, 40)            # 120 tokens: 8 dispatch blocks of 16
    got = prefill_last(mc, params, toks)
    want = np.asarray(ref.last_logits(params, toks, cfg, 1)[:, 0])
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 12)])
def test_prefill_then_decode_match_reference(held, first):
    """Prefill, then decode steps through the donated cache, read the
    reference's logits of the whole sequence at each position."""
    from repro.runtime.serve_loop import _decode_program

    cfg, mc = small(held=held, first=first)
    params = weights.draw(ref.param_specs(cfg), 12, jnp.float32)
    B, T, n = 2, 21, 4
    toks = tokens(B, T + n, seed=1)
    model = build_model(mc)
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(toks[:, :T])})
    full = model.init_cache(B, T + n)
    cache = tuple(jnp.pad(c, [(0, w - g) for g, w in zip(c.shape, f.shape)])
                  for c, f in zip(cache, full))
    got = [np.asarray(logits[:, -1])]
    decode = _decode_program(mc)
    for i in range(n - 1):
        logits, cache, counts = decode(params, cache,
                                       jnp.asarray(toks[:, T + i:T + i + 1]),
                                       jnp.int32(T + i))
        got.append(np.asarray(logits[:, -1]))
        rows, active = counts.tolist()
        assert 0 < active <= mc.n_layers * held
        assert active <= rows <= mc.n_layers * B * min(mc.top_k, held)
    want = np.asarray(ref.last_logits(params, toks[:, :T + n - 1], cfg, n))
    np.testing.assert_allclose(np.stack(got, 1), want, atol=ATOL, rtol=0)


def test_shares_add_up_to_uncut_layer():
    """Eight devices of two experts each: their parts of one layer's
    output add up to the layer that holds all sixteen, and to the
    reference's uncut layer."""
    cfg, mc = small(held=16, first=0)
    params = weights.draw(ref.param_specs(cfg), 13, jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    whole, _, whole_counts = moe_ffn(x, lp, mc)
    parts, rows = 0.0, 0
    for s in range(8):
        share = dataclasses.replace(mc, experts_held=2, first_expert=2 * s)
        sp = dict(lp, **{k: lp[k][2 * s:2 * s + 2]
                         for k in ("w_gate", "w_up", "w_down")})
        out, _, counts = moe_ffn(x, sp, share)
        parts = parts + out
        rows += counts.tolist()[0]
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert rows == whole_counts.tolist()[0] == 2 * 24 * mc.top_k
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts(x, lp["router"], lp["w_gate"], lp["w_up"],
                            lp["w_down"], k=mc.top_k, first=0,
                            rnd=lambda name, a: a)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut),
                               atol=1e-5, rtol=0)


def test_router_forced_onto_one_expert_matches_reference():
    """A router of zeros ties every expert, and top-1 takes the first:
    every token of every layer goes to one held expert. Capacity dispatch
    would drop most of them; the dropless layer computes them all, as the
    reference does."""
    cfg, mc = small(held=4, first=0, k=1)
    params = weights.draw(ref.param_specs(cfg), 14, jnp.float32)
    params["layers"]["ffn"]["router"] = jnp.zeros_like(
        params["layers"]["ffn"]["router"])
    x = jax.random.normal(jax.random.PRNGKey(6), (96, 64), jnp.float32)
    _, local, held, _ = route(x, params["layers"]["ffn"]["router"][0], mc)
    assert bool(jnp.all(held & (local == 0)))
    toks = tokens(2, 48, seed=2)
    got = prefill_last(mc, params, toks)
    want = np.asarray(ref.last_logits(params, toks, cfg, 1)[:, 0])
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_qk_norm_scales_change_logits_and_reference_follows():
    cfg, mc = small(held=4, first=0)
    params = weights.draw(ref.param_specs(cfg), 15, jnp.float32)
    ones = jax.tree.map(lambda a: a, params)
    for name in ("q_norm", "k_norm"):
        ones["layers"]["attn"][name] = jnp.ones_like(
            params["layers"]["attn"][name])
    toks = tokens(2, 30, seed=3)
    drawn, unit = prefill_last(mc, params, toks), prefill_last(mc, ones, toks)
    assert np.max(np.abs(drawn - unit)) > 1e-2
    for p, got in ((params, drawn), (ones, unit)):
        want = np.asarray(ref.last_logits(p, toks, cfg, 1)[:, 0])
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_routing_counts_match_a_plain_count():
    cfg, mc = small(held=4, first=4)
    params = weights.draw(ref.param_specs(cfg), 16, jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 64), jnp.float32)
    _, _, counts = moe_ffn(x, lp, mc)
    probs = jax.nn.softmax(x.reshape(21, 64) @ lp["router"], axis=-1)
    top = np.asarray(jax.lax.top_k(probs, mc.top_k)[1])
    mine = top[(top >= 4) & (top < 8)]
    assert counts.tolist() == [mine.size, len(set(mine.tolist()))]


def test_adapter_refuses_mismatched_head_dim():
    """The registry's entry before QK-norm (head_dim 64, no QK-norm)
    against the cell's file: the run stops, naming both."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    old = dataclasses.replace(get_config("qwen3-moe-30b-a3b"), d_head=64,
                              qk_norm=False)
    with pytest.raises(ValueError, match="d_head.*qk_norm"):
        adapter.program_config(cfg, base=old)
    with pytest.raises(ValueError, match="expert_share"):
        adapter.program_config(dict(cfg, num_experts=200))


def test_adapter_keeps_the_published_widths():
    with open(CONFIG) as f:
        cfg = json.load(f)
    mc = adapter.program_config(cfg)
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.d_head, mc.d_ff,
            mc.vocab) == (2048, 32, 4, 128, 768, 151936)
    assert (mc.n_experts, mc.top_k, mc.n_held_experts, mc.first_expert,
            mc.n_layers, mc.qk_norm) == (128, 8, 16, 0, 16, True)
    # the file's weight tree is the program's, leaf for leaf
    specs = ref.param_specs(cfg)
    defs = build_model(mc).param_defs()
    assert jax.tree.map(lambda s: s[0], specs,
                        is_leaf=lambda s: isinstance(s, tuple)) == \
        jax.tree.map(lambda d: d.shape, defs,
                     is_leaf=lambda d: hasattr(d, "shape"))


def test_generate_counts_routing_once_per_request():
    """``generate`` adds a MoE model's decode routing to ``moe.rows`` and
    ``moe.active``; a dense model adds nothing."""
    from repro.runtime.serve_loop import ServeConfig, generate

    cfg, mc = small(held=16, first=0)
    params = weights.draw(ref.param_specs(cfg), 17, jnp.float32)
    B, n = 3, 5
    before = telemetry.snapshot()
    out = generate(mc, {"tokens": tokens(B, 12), "params": params},
                   ServeConfig(max_new_tokens=n))
    after = telemetry.snapshot()
    d = {k: after[k]["n"] - before.get(k, {"n": 0})["n"]
         for k in ("moe.rows", "moe.active")}
    assert out["tokens"].shape == (B, n)
    assert d["moe.rows"] == (n - 1) * mc.n_layers * B * mc.top_k
    assert (n - 1) * mc.n_layers <= d["moe.active"] <= d["moe.rows"]
    dense = get_config("deepseek-7b").reduced()
    before = telemetry.snapshot()
    generate(dense, {"tokens": tokens(1, 8)},
             ServeConfig(max_new_tokens=3))
    after = telemetry.snapshot()
    assert all(after.get(k) == before.get(k) for k in ("moe.rows",
                                                      "moe.active"))


def test_work_counts():
    cfg, _ = small(held=4, first=4, router=16, k=4)
    n = ref.dims(cfg)
    specs = ref.param_specs(cfg)
    leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, tuple))
    assert ref.weight_bytes(cfg) == 4 * sum(int(np.prod(s[0])) for s in leaves)
    # uniform routing: a token has k * Eh / E held rows a layer
    assert ref.expert_rows_per_token(cfg) == n["L"] * 4 * 4 / 16
    assert ref.expert_reads(cfg, 1) == pytest.approx(n["L"] * 4 * 4 / 16)
    assert ref.expert_reads(cfg, 10 ** 6) == pytest.approx(n["L"] * 4)
    f0, b0 = ref.decode_step(cfg, 3, 50, rows=0, reads=0)
    f, b = ref.decode_step(cfg, 3, 50, rows=7, reads=5)
    assert f - f0 == 2 * 7 * ref.expert_params(cfg)
    assert b - b0 == 5 * ref.expert_params(cfg) * 4
    fe, be = ref.decode_step(cfg, 3, 50)
    assert fe - f0 == pytest.approx(
        2 * 3 * ref.expert_rows_per_token(cfg) * ref.expert_params(cfg))
    assert be - b0 == pytest.approx(
        ref.expert_reads(cfg, 3) * ref.expert_params(cfg) * 4)


def _run(cfg, before, after, requests):
    import types

    cell = types.SimpleNamespace(family=ref, config=cfg)
    return types.SimpleNamespace(
        cell=cell, peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        requests=requests, tuning_before={"telemetry": before},
        tuning_after={"telemetry": after})


def test_moe_metrics_read_the_counters():
    cfg, _ = small(held=4, first=4, router=16, k=4)
    path = os.path.join(ROOT, "chipbench", "metrics")
    read = {}
    for name in ("moe.rows_per_read", "moe.decode_roofline_pct"):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            name.replace(".", "_"), os.path.join(path, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        read[name] = mod.read
    req = [{"batch": 3, "prompt_len": 40, "new_tokens": 6, "decode_s": 1.0}]
    t0 = {"moe.rows": {"s": 0.0, "n": 10}, "moe.active": {"s": 0.0, "n": 4}}
    t1 = {"moe.rows": {"s": 0.0, "n": 70}, "moe.active": {"s": 0.0, "n": 24}}
    run = _run(cfg, t0, t1, req)
    assert read["moe.rows_per_read"](run) == 60 / 20
    # the least time, by hand: five steps, bytes-bound at these peaks
    flops = 2 * 60 * ref.expert_params(cfg)
    byt = 20 * ref.expert_params(cfg) * 4
    for i in range(1, 6):
        f, b = ref.decode_step(cfg, 3, 40 + i, rows=0, reads=0)
        flops, byt = flops + f, byt + b
    least = max(flops / 1e12, byt / 1e11)
    assert read["moe.decode_roofline_pct"](run) == pytest.approx(100 * least)
    assert spans.delta(run, "moe.rows")["n"] == 60
    # a program without the counters reads nothing
    quiet = _run(cfg, {}, {}, req)
    assert read["moe.rows_per_read"](quiet) is None
    assert read["moe.decode_roofline_pct"](quiet) is None
