"""The check that decides ``correct``, at CPU size: the timed path
through ``generate`` agrees with the plain reference, the reference at a
lower precision put in its place does not, and a run with its timed path
broken underneath reads ``correct`` false.

At this size the gaps are of another scale than at the published widths
(a vocabulary of 512, two layers), so the tests hold them to a limit of
their own, between the program's readings here (mean gap up to 3e-4)
and fp8's (0.008 and up); the cells' limits come from readings on the
chip, where ``calibrate.py`` judges each control by the cell's own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, weights
from conftest import run_tiny, tiny_cell

CELLS = ["ds7b.short", "dsc33b.long"]


TINY_LIMITS = {"mean_gap": 0.002}


def cell_for(name):
    # dsc33b.long's GQA is kept: 4 query heads over 2 KV heads
    return tiny_cell(name, kv_heads=4 if name == "ds7b.short" else 2,
                     limits=TINY_LIMITS)


@pytest.mark.parametrize("name", CELLS)
def test_timed_path_agrees_with_reference(name):
    out = run_tiny(cell_for(name), seed=2 ** 32 + 11)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 4


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_in_its_place_fails(name):
    cell = cell_for(name)
    seed = 2 ** 32 + 12
    out = run_tiny(cell, seed=seed, keep_sample=True)
    params = weights.draw(cell.family.param_specs(cell.config), seed,
                          jnp.bfloat16)
    control = check.numbers(check.gaps(cell.family, params, cell.config,
                                       out["sample"], precision="fp8"))
    assert out["correct"], out["check"]
    held, shown = check.judge(control, cell.check["limits"])
    assert not held, shown


def _altered_token(model_cls):
    decode = model_cls.decode_step

    def step(self, params, cache, tokens, pos, rope_pos=None):
        logits, cache = decode(self, params, cache, tokens, pos, rope_pos)
        low = jnp.argmin(logits[0, -1])
        hit = (pos % 3) == 1
        return logits.at[0, -1, low].add(jnp.where(hit, 1e4, 0.0)), cache
    return step


def _state_unchanged(model_cls):
    decode = model_cls.decode_step

    def step(self, params, cache, tokens, pos, rope_pos=None):
        logits, _ = decode(self, params, cache, tokens, pos, rope_pos)
        return logits, cache
    return step


def _half_batch(model_cls):
    prefill = model_cls.prefill

    def step(self, params, batch):
        tok = batch["tokens"]
        half = tok.shape[0] // 2
        kept = jnp.concatenate([tok[:half]] * 2, axis=0)
        return prefill(self, params, dict(batch, tokens=kept))
    return step


@pytest.mark.parametrize("fault,method", [
    (_altered_token, "decode_step"),
    (_state_unchanged, "decode_step"),
    (_half_batch, "prefill"),
])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, method):
    from repro.models.transformer import TransformerLM

    jax.clear_caches()
    monkeypatch.setattr(TransformerLM, method, fault(TransformerLM))
    out = run_tiny(cell_for("dsc33b.long"), seed=2 ** 32 + 13)
    jax.clear_caches()
    assert out["attempted"] >= 4
    assert not out["correct"], out["check"]


def test_sample_holds_a_longest_request_and_enough_tokens():
    recs = [{"prompt_len": n, "served": np.zeros((1, k))}
            for n, k in [(8, 5), (16, 9), (8, 5), (32, 2), (16, 9), (32, 2),
                         (8, 5)]]
    for seed in range(20):
        picked = check.sample(recs, 12, seed)
        assert len(set(picked)) == len(picked)
        assert any(recs[i]["prompt_len"] == 32 for i in picked)
        tokens = sum(recs[i]["served"].size for i in picked)
        assert tokens >= 12
        # no request more than needed: dropping the last drawn falls short
        assert tokens - min(recs[i]["served"].size for i in picked) < 12 + 9
    assert check.sample(recs, 12, 5) == check.sample(recs, 12, 5)
    assert check.sample([], 12, 5) == []
    assert check.sample(recs, 10 ** 6, 1) == list(range(len(recs)))


def test_gaps_stack_requests_of_one_shape():
    class Fam:
        calls = []

        @staticmethod
        def last_logits(params, seq, cfg, n, precision=None):
            Fam.calls.append(seq.shape)
            # logits favour token 0 by 1.0 over token 1 at every position
            out = np.zeros(seq.shape[:1] + (n, 3), np.float32)
            out[..., 0] = 1.0
            return jnp.asarray(out)

    pairs = [(np.zeros((1, 4), np.int32), np.array([[0, 1, 0]])),
             (np.zeros((1, 4), np.int32), np.array([[1, 1, 1]])),
             (np.zeros((1, 6), np.int32), np.array([[0, 0]]))]
    g = check.gaps(Fam, {}, {}, pairs)
    assert sorted(Fam.calls) == [(1, 7), (2, 6)]
    assert sorted(g.tolist()) == [0.0] * 4 + [1.0] * 4
    assert check.numbers(g) == {"mean_gap": 0.5, "max_gap": 1.0}
    held, shown = check.judge(check.numbers(g), {"mean_gap": 0.4})
    assert not held and shown == {"mean_gap": {"value": 0.5, "limit": 0.4}}
