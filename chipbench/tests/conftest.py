"""CPU fixtures: the benchmark's cells at a size a test run can hold."""

import dataclasses
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(workload: str, *, d: int = 128, layers: int = 2,
              vocab: int = 512, heads: int = 4, kv_heads: int = 2,
              ff: int = 256, prompt=(32, 0.5), output=(8, 0.4),
              batch: int = 2, limits=None):
    """``workload``'s files with every size cut to CPU scale; the program
    sees the same cut through a reduced entry of its own registry. The
    check keeps the cell's own limits unless ``limits`` replaces them."""
    from chipbench import harness
    from chipbench.adapters import dense as adapter
    from repro.configs import get_config

    cell = harness.load_cell(workload)
    cfg = dict(cell.config, hidden_size=d, intermediate_size=ff,
               num_attention_heads=heads, num_key_value_heads=kv_heads,
               num_hidden_layers=layers, vocab_size=vocab)
    base = get_config(cfg["program_arch"]).reduced(
        d_model=d, d_head=d // heads, n_heads=heads, n_kv_heads=kv_heads,
        d_ff=ff, vocab=vocab)
    mix = dict(cell.mix, batch=batch, strata=2, pairing=[1, 0],
               prompt={"median": prompt[0], "sigma": prompt[1], "min": 8,
                       "max": 64},
               output={"median": output[0], "sigma": output[1], "min": 2,
                       "max": 16},
               trace_requests=2)
    return dataclasses.replace(
        cell, config=cfg, mix=mix,
        check=dict(cell.check, sample_tokens=64,
                   limits=limits or cell.check["limits"]),
        adapter=types.SimpleNamespace(
            program_config=lambda c: adapter.program_config(c, base=base)))


def run_tiny(cell, seed: int, seconds: float = 1.5, **kw):
    from chipbench import harness

    return harness.run(cell, seed, seconds, False, t_proc0=time.perf_counter(),
                       peak=PEAK, log=lambda s: None, **kw)


@pytest.fixture(scope="session")
def tiny():
    return tiny_cell
