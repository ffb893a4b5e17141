"""Pieces of the harness: the percentile, the cell loader, and the
compile-cache priming that must leave the tuners as they were."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, weights
from conftest import tiny_cell


def test_nearest_rank_percentile():
    v = list(range(1, 11))            # 1..10
    assert harness.percentile(v, 90) == 9
    assert harness.percentile(v, 50) == 5
    assert harness.percentile(v, 100) == 10
    assert harness.percentile([7.5], 90) == 7.5
    assert harness.percentile(list(range(1, 55)), 90) == 49


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no.such.cell")


def test_priming_leaves_the_tuners_proposals():
    from repro.runtime.serve_loop import ServeConfig, generate

    cell = tiny_cell("ds7b.short", kv_heads=4)
    model_cfg = cell.adapter.program_config(cell.config)
    params = weights.draw(cell.family.param_specs(cell.config), 3,
                          jnp.bfloat16)
    sink, handles = {}, []
    session, tcfg = harness._session(cell.mix, sink, handles)
    try:
        tokens = np.zeros((2, 24), np.int32)
        generate(model_cfg, {"tokens": tokens, "params": params},
                 ServeConfig(max_new_tokens=8, tuning=tcfg), session=session)
        assert "t_first" in sink
        assert [h.name for h in handles] == ["serve_prefill", "serve_decode"]
        before = [h.tuner.explorer.peek(3) for h in handles]
        n = harness.prime_compile_cache(handles, 3)
        assert n == sum(len(p) for p in before) > 0
        assert [h.tuner.explorer.peek(3) for h in handles] == before
        assert [h.tuner.explorer.next_point() for h in handles] == [
            p[0] for p in before]
    finally:
        session.close()
