"""Compile a cell's largest programs for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell>

Compiles, for one chip of a described v5e, the program's prefill step at
the mix's batch and longest prompt, its decode step at the mix's largest
cache, and the reference's layer at the rows the check stacks of that
request, and prints each one's ``memory_analysis()`` beside the weights'
bytes. Nothing runs, so this says nothing of times or results; it finds
a program that the chip's compiler refuses or that does not fit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import check, harness
    from chipbench.traffic import ClosedLoop
    from repro.models.model import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    cfg, fam = cell.config, cell.family
    model_cfg = cell.adapter.program_config(cfg)
    model = build_model(model_cfg)
    loop = ClosedLoop(cell.mix, cfg["vocab_size"], 0)
    B, T = loop.batch, max(t for t, _ in loop.block)
    big_t, big_n = loop.longest

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda leaf: sds(leaf[0], model_cfg.param_dtype),
        fam.param_specs(cfg), is_leaf=lambda x: isinstance(x, tuple))
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    cache = tuple(sds(s.shape, s.dtype)
                  for s in model.init_cache_shape(B, big_t + big_n))

    def report(name, compiled):
        m = compiled.memory_analysis()
        row = {"program": name,
               "argument_bytes": m.argument_size_in_bytes,
               "output_bytes": m.output_size_in_bytes,
               "alias_bytes": m.alias_size_in_bytes,
               "temp_bytes": m.temp_size_in_bytes,
               "weights_bytes": weight_bytes}
        print(json.dumps(row), flush=True)

    report(f"prefill B={B} T={T}", jax.jit(model.prefill).lower(
        params, {"tokens": sds((B, T), jnp.int32)}).compile())
    report(f"decode B={B} S={big_t + big_n}", jax.jit(model.decode_step).lower(
        params, cache, sds((B, 1), jnp.int32), sds((), jnp.int32)).compile())
    embed, layer, head = fam._compiled(
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        json.dumps(cfg.get("rope_scaling"), sort_keys=True), None)
    S = big_t + big_n - 1
    rows = B * max(check.MAX_ROWS_TOKENS // (big_t + big_n), 1)
    with jax.default_matmul_precision("highest"):
        report(f"reference layer B={rows} S={S}", layer.lower(
            sds((rows, S, cfg["hidden_size"]), jnp.float32), params["layers"],
            sds((), jnp.int32)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
