"""The MoE cell at CPU size: its mix, its stage's sizes, and the check
that decides ``correct`` at batch 24 (the timed path through
``generate`` agrees with the plain reference, the reference at fp8 in its
place does not).

The gaps here are of another scale than at the published widths, so the
check is held to a limit of its own, between the program's readings at
this size and fp8's: over seeds 2**32 + 18 .. 27, the program's mean gap
read 0.00004-0.0040 (a bf16 rounding can swap a token's experts, which
moves this two-layer model's logits more than the dense cells' tiny
ones, 3e-4), int8's 0.0012-0.0097 (too close to tell apart here) and
fp8's 0.0177-0.048."""

import dataclasses
import json
import os
import types

import jax.numpy as jnp
import pytest

from chipbench import check, harness, weights
from chipbench.adapters import moe as adapter
from chipbench.reference import moe as ref
from chipbench.traffic import ClosedLoop
from conftest import run_tiny

CELL = "qwen3moe.chat.b24"
TINY_LIMITS = {"mean_gap": 0.01}


def tiny_moe_cell(batch: int = 24):
    """The cell's files cut to CPU scale: 16 router outputs of which 4 are
    held (from 4 on), top 4; the program sees the same cut through a
    reduced entry of its registry."""
    from repro.configs import get_config

    cell = harness.load_cell(CELL)
    cfg = dict(cell.config, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               num_hidden_layers=2, vocab_size=512, num_experts=4,
               num_experts_per_tok=4,
               expert_share=dict(cell.config["expert_share"],
                                 router_experts=16, first_expert=4))
    base = get_config(cfg["program_arch"]).reduced(
        d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, vocab=512,
        n_experts=16, top_k=4)
    mix = dict(cell.mix, batch=batch, strata=2, pairing=[1, 0],
               prompt={"median": 32, "sigma": 0.5, "min": 8, "max": 64},
               output={"median": 8, "sigma": 0.4, "min": 2, "max": 16},
               trace_requests=2)
    return dataclasses.replace(
        cell, config=cfg, mix=mix,
        check=dict(cell.check, sample_tokens=64, limits=TINY_LIMITS),
        adapter=types.SimpleNamespace(
            program_config=lambda c: adapter.program_config(c, base=base)))


def test_the_mix_is_azure_conv_at_batch_24():
    cell = harness.load_cell(CELL)
    with open(os.path.join(harness.BENCH_DIR, "traffic",
                           "azure_conv.json")) as f:
        conv = json.load(f)
    assert {k: v for k, v in cell.mix.items() if k not in ("batch", "why")} \
        == {k: v for k, v in conv.items() if k not in ("batch", "why")}
    loop = ClosedLoop(cell.mix, cell.config["vocab_size"], 2 ** 33 + 1)
    assert loop.block == ClosedLoop(conv, 151936, 1).block
    assert [t for t, _ in sorted(loop.block)] == [366, 671, 1020, 1552, 2844]
    _, tokens, _ = next(loop.requests())
    assert tokens.shape[0] == 24
    # the check's sample is one request of the longest prompt: 24 x 41
    assert loop.longest == (2844, 41)
    assert 24 * 41 >= cell.check["sample_tokens"]


def test_stage_sizes():
    c = harness.load_cell(CELL).config
    # a layer: 2048 x (32 + 2 x 4) x 128 + 32 x 128 x 2048 (attention),
    # 2 x 128 (QK-norm), 2048 x 128 (router), 2 x 2048 (norms): 19.14 M;
    # 16 experts of 3 x 2048 x 768: 75.50 M; embedding and head 311.2 M
    assert ref.attn_params(c) == (2048 * 40 * 128 + 32 * 128 * 2048
                                  + 2 * 128 + 2048 * 128 + 2 * 2048)
    layer = ref.attn_params(c) + 16 * ref.expert_params(c)
    assert round(layer / 1e6, 2) == 94.64
    assert ref.weight_bytes(c) == 2 * (16 * layer + 2 * 151936 * 2048 + 2048)
    assert round(ref.weight_bytes(c) / 1e9, 2) == 4.27
    assert ref.kv_bytes_per_token(c) == 32 * 1024
    # batch 24: each held expert is hit with probability 1 - (1 - 8/128)^24
    assert round(ref.expert_reads(c, 24) / (16 * 16), 3) == 0.788
    assert ref.expert_rows_per_token(c) == 16 * 8 * 16 / 128
    # a step at S = 1106: held experts 1.90e9 B, attention 0.61e9, the
    # head 0.62e9 (151936 x 2048 x 2), the cache 0.87e9: 4.9 ms at 819 GB/s
    f, b = ref.decode_step(c, 24, 1106)
    assert 3.9e9 < b < 4.1e9


def test_timed_path_agrees_with_reference_at_batch_24():
    out = run_tiny(tiny_moe_cell(), seed=2 ** 32 + 21)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 2


def test_lower_precision_in_its_place_fails():
    cell = tiny_moe_cell()
    seed = 2 ** 32 + 22
    out = run_tiny(cell, seed=seed, keep_sample=True)
    assert out["correct"], out["check"]
    assert all(p.shape[0] == 24 for p, _ in out["sample"])
    params = weights.draw(cell.family.param_specs(cell.config), seed,
                          jnp.bfloat16)
    control = check.numbers(check.gaps(cell.family, params, cell.config,
                                       out["sample"], precision="fp8"))
    held, shown = check.judge(control, cell.check["limits"])
    assert not held, shown


def test_an_older_program_stops_the_run():
    """The registry's entry before QK-norm, with its head_dim of 64."""
    from repro.configs import get_config

    old = dataclasses.replace(get_config("qwen3-moe-30b-a3b"), d_head=64)
    with pytest.raises(ValueError, match="d_head"):
        adapter.program_config(harness.load_cell(CELL).config, base=old)
