"""Real-width compiles of the serve path's kernels for a described v5e.

Each catalog kernel the kernel plane attaches for deepseek-7b (batch 2,
128-token prompts, 16 new tokens, bf16) is compiled by the TPU compiler
at the smallest and the largest point of its tuning space, for a chip
that is described and not attached. A refusal here (a VMEM limit, a
misaligned tile) is what the chip itself would raise; a Pallas kernel
must come out as a Mosaic kernel (``tpu_custom_call``), never in
interpret mode. The served decode step is compiled there too, to see
that it updates its cache in place. All of these compiles live in this
one file: the TPU library may be loaded by one process at a time.
"""

import dataclasses
import os

import pytest

PALLAS = ("attention", "matmul", "rmsnorm")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _serve_specs() -> dict:
    """The specs ``attach_kernels`` registers when serving deepseek-7b."""
    import jax.numpy as jnp

    from repro.api import serve_tuning_defaults
    from repro.configs import get_config
    from repro.models.model import model_kernel_specs

    cfg = dataclasses.replace(get_config("deepseek-7b"),
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    lifecycle = serve_tuning_defaults().lifecycle()
    return dict(model_kernel_specs(
        cfg, batch=2, seq=lifecycle.bucket_length(128),
        max_len=lifecycle.bucket_length(128 + 16)))


@pytest.mark.parametrize("end", ["smallest", "largest"])
@pytest.mark.parametrize("name", ["attention", "decode_attention",
                                  "matmul", "rmsnorm"])
def test_kernel_compiles_for_v5e_at_deepseek_width(name, end, one_chip):
    import jax

    from repro.kernels.catalog import get_catalog

    spec = _serve_specs()[name]
    assert spec["dtype"] == "bfloat16"
    defn = get_catalog().get(name)
    valid = list(defn.make_space(spec).iter_valid())
    point = valid[0] if end == "smallest" else valid[-1]
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in defn.abstract_args(spec))
    fn = defn.generate(dict(point), spec, interpret=False)
    compiled = fn.lower(*args).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == (name in PALLAS), (name, point)


def test_decode_step_updates_its_cache_in_place_on_v5e(one_chip):
    """The served decode step at deepseek-7b width and the longest
    ``ds7b.short`` cache (2885 positions): the donated cache aliases the
    output, and no op copies or bit-converts a whole stacked cache (a
    bit view of it gives the scan carry a layout padded four times)."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models.params import init_tree
    from repro.runtime.serve_loop import _decode_program

    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=2,
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    model = build_model(cfg)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(lambda: init_tree(
        model.param_defs(), jax.random.PRNGKey(0), cfg.param_dtype)))
    cache = tuple(sds(c) for c in model.init_cache_shape(1, 2885))
    cache_bytes = sum(c.size * c.dtype.itemsize for c in cache)
    compiled = _decode_program(cfg).lower(
        params, cache, sds(jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        sds(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    whole = "[" + ",".join(map(str, cache[0].shape)) + "]"
    copies = [line.strip()[:120] for line in compiled.as_text().splitlines()
              if re.search(r"=\s*\w+" + re.escape(whole)
                           + r"\S*\s+(copy|bitcast-convert)\(", line)]
    assert not copies, copies


def test_moe_decode_reads_its_experts_in_place_on_v5e(one_chip):
    """The served decode step of qwen3-moe-30b-a3b at its published widths,
    as ``qwen3moe.chat.b24`` holds it (16 of 128 experts, batch 24): the
    expert matmuls are ragged dots, which the TPU compiler makes Mosaic
    grouped matmuls, and no op copies a layer's held experts out of the
    stacks (a kernel's sliced operand would be one copy a layer a step)."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models.params import init_tree
    from repro.runtime.serve_loop import _decode_program

    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b"), n_layers=2,
                              experts_held=16, param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    model = build_model(cfg)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(lambda: init_tree(
        model.param_defs(), jax.random.PRNGKey(0), cfg.param_dtype)))
    cache = tuple(sds(c) for c in model.init_cache_shape(24, 2885))
    text = _decode_program(cfg).lower(
        params, cache, sds(jax.ShapeDtypeStruct((24, 1), jnp.int32)),
        sds(jax.ShapeDtypeStruct((), jnp.int32))).compile().as_text()
    grouped = [line for line in text.splitlines()
               if re.match(r"\s*%ragged-dot-none", line)]
    assert len(grouped) == 3 and all("tpu_custom_call" in line
                                     for line in grouped)
    experts = [line.strip()[:120] for line in text.splitlines()
               if re.search(r"=\s*bf16\[16,(2048,768|768,2048)\]", line)]
    assert not experts, experts


@pytest.mark.parametrize("name,B,T,chunks", [
    ("deepseek-7b", 1, 2844, None),
    ("deepseek-coder-33b", 1, 5403, None),
    ("qwen3-moe-30b-a3b", 24, 2844, None),
    ("qwen3-moe-30b-a3b", 24, 100, None),
    ("deepseek-coder-33b", 1, 45, None),
    ("deepseek-coder-33b", 1, 5403, (32, 32)),
])
def test_prefill_attention_is_one_kernel_on_v5e(name, B, T, chunks,
                                                one_chip):
    """The served prefill at the cells' widths and batches (two layers),
    at their longest prompts, at short ragged ones whose G heads stack at
    offsets off the sublane tiling, and at the prefill tuner's smallest
    tiles: attention is the fused Mosaic kernel (GQA groups of 1, 7 and
    8), and no float32 score block of the program's chunks
    (``[..., q_chunk, k_chunk]``) is left in it."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models.params import init_tree

    cfg = dataclasses.replace(get_config(name), n_layers=2,
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, experts_held=16)
    if chunks:
        cfg = dataclasses.replace(cfg, attn_q_chunk=chunks[0],
                                  attn_k_chunk=chunks[1])
    model = build_model(cfg)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(lambda: init_tree(
        model.param_defs(), jax.random.PRNGKey(0), cfg.param_dtype)))
    text = jax.jit(model.prefill).lower(
        params, {"tokens": sds(jax.ShapeDtypeStruct((B, T), jnp.int32))}
    ).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn.core" in line]
    assert len(kernels) == 1
    scores = re.findall(r"f32\[[0-9,]*\b%d,%d\]" % (
        cfg.attn_q_chunk, cfg.attn_k_chunk), text)
    assert not scores, scores
