"""Per-architecture smoke tests + family-specific invariants.

Each assigned architecture is instantiated at a REDUCED config of the same
family and runs one forward/train step on CPU asserting output shapes and
the absence of NaNs. Decode paths are checked for consistency against a
longer prefill.
"""

import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models.model import build_model
from repro.models.params import count_params, init_tree

ARCHS = sorted(REGISTRY)
MODELS_DIR = pathlib.Path(__file__).resolve().parent.parent / "src" / \
    "repro" / "models"
MODEL_MODULES = sorted(p.stem for p in MODELS_DIR.glob("*.py"))
# the layers above the models: the tuner, the front door, the benchmark
# harnesses and the launchers
UPPER_LAYERS = ("repro.runtime", "repro.api", "repro.bench", "repro.launch")


def make_batch(cfg, B=2, T=32, key=jax.random.PRNGKey(1)):
    batch = {
        "tokens": jax.random.randint(key, (B, T), 0, cfg.vocab),
    }
    batch["labels"] = batch["tokens"]
    if cfg.family == "encdec":
        batch["audio_embeds"] = jax.random.normal(
            key, (B, cfg.enc_frames, cfg.d_model)) * 0.05
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(key, (B, 16, cfg.d_model)) * 0.05
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_loss_and_grads(arch):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg)
    params = init_tree(model.param_defs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_prefill_decode_consistency(arch):
    """decode(prefill(T), token_T) == prefill(T+1) last logits.

    The MoE layer is dropless, so a token's expert output does not depend
    on the tokens it is dispatched with: no setting is needed for MoE.
    """
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg)
    params = init_tree(model.param_defs(), jax.random.PRNGKey(0))
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab)

    def mk(t):
        b = {"tokens": t, "labels": t}
        if cfg.family == "encdec":
            b["audio_embeds"] = jax.random.normal(
                jax.random.PRNGKey(2), (B, cfg.enc_frames, cfg.d_model)) * 0.05
        if cfg.family == "vlm":
            b["vision"] = jax.random.normal(
                jax.random.PRNGKey(3), (B, 16, cfg.d_model)) * 0.05
        return b

    logits_p, cache = jax.jit(model.prefill)(params, mk(toks[:, :T]))
    assert bool(jnp.all(jnp.isfinite(logits_p)))
    full = model.init_cache(B, 64)
    widened = []
    for got, want in zip(cache, full):
        if got.shape == want.shape:
            widened.append(got.astype(want.dtype))
        else:
            pads = [(0, w - g) for g, w in zip(got.shape, want.shape)]
            widened.append(jnp.pad(got, pads).astype(want.dtype))
    pos0 = T if cfg.family != "vlm" else T + 16
    logits_d, new_cache = jax.jit(model.decode_step)(
        params, tuple(widened), toks[:, T:T + 1], jnp.int32(pos0))
    logits_p2, _ = jax.jit(model.prefill)(params, mk(toks))
    np.testing.assert_allclose(
        np.asarray(logits_p2, np.float32), np.asarray(logits_d, np.float32),
        rtol=2e-2, atol=2e-2)


def test_param_counts_match_analytic():
    for arch in ARCHS:
        cfg = REGISTRY[arch]
        model = build_model(cfg)
        analytic = cfg.n_params()
        from repro.models.params import _iter_defs
        exact = count_params(model.param_defs())
        # analytic formula ignores norms/small vectors: within 5 %
        assert abs(exact - analytic) / exact < 0.05, (arch, exact, analytic)


def test_rwkv_chunk_invariance():
    cfg = REGISTRY["rwkv6-1.6b"].reduced()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    losses = []
    for chunk in (4, 8, 24, 7):   # incl. ragged chunking
        m = build_model(dataclasses.replace(cfg, scan_chunk=chunk))
        params = init_tree(m.param_defs(), jax.random.PRNGKey(0))
        losses.append(float(jax.jit(m.loss)(params, batch)))
    for l in losses[1:]:
        assert abs(l - losses[0]) < 1e-4, losses


def test_hymba_ssm_chunk_invariance():
    cfg = REGISTRY["hymba-1.5b"].reduced()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    losses = []
    for chunk in (4, 12, 24):
        m = build_model(dataclasses.replace(cfg, scan_chunk=chunk))
        params = init_tree(m.param_defs(), jax.random.PRNGKey(0))
        losses.append(float(jax.jit(m.loss)(params, batch)))
    for l in losses[1:]:
        assert abs(l - losses[0]) < 1e-4, losses


def test_moe_aux_loss_and_capacity(monkeypatch):
    """The aux loss is over the full router, and nothing is dropped for
    capacity: with every token routed to the same experts, tokens
    dispatched in blocks of 8 get the output each gets alone."""
    from repro.models import moe
    cfg = REGISTRY["qwen3-moe-30b-a3b"].reduced()
    m = build_model(cfg)
    params = init_tree(m.param_defs(), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    out, aux, counts = moe.moe_ffn(x, lp["ffn"], cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(aux)) and float(aux) > 0
    assert counts.tolist()[0] == 2 * 32 * cfg.top_k   # every choice held
    # a router of zeros ties every expert: top-2 takes experts 0 and 1
    p1 = dict(lp["ffn"], router=jnp.zeros_like(lp["ffn"]["router"]))
    monkeypatch.setattr(moe, "BLOCK_TOKENS", 8)
    crowded, _, counts = moe.moe_ffn(x, p1, cfg)
    assert counts.tolist() == [2 * 32 * 2, 2]
    alone = jnp.stack([jnp.concatenate(
        [moe.moe_ffn(x[b:b + 1, t:t + 1], p1, cfg)[0] for t in range(32)],
        axis=1)[0] for b in range(2)])
    np.testing.assert_allclose(np.asarray(crowded), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)


def test_vlm_mrope_positions():
    from repro.models.vlm import mrope_positions
    pos = mrope_positions(16, 8, 2)
    assert pos.shape == (3, 2, 24)
    # text positions strictly increase on every stream
    txt = pos[:, 0, 16:]
    assert bool(jnp.all(txt[:, 1:] > txt[:, :-1]))


def test_rope_pair_locality():
    """Interleaved-pair RoPE: rotating a head dim sharded in pair units is
    equivalent to rotating the full head dim (no cross-pair mixing)."""
    from repro.models.layers import apply_rope
    B, T, H, Dh = 1, 8, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, Dh))
    pos = jnp.arange(T)[None]
    full = apply_rope(x, pos, 1e4)
    # pairs (2i, 2i+1) only mix among themselves
    x2 = x.at[..., 2:].set(0)
    part = apply_rope(x2, pos, 1e4)
    np.testing.assert_allclose(part[..., :2], full[..., :2], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(part[..., 2:], jnp.zeros_like(part[..., 2:]),
                               atol=1e-6)


def _imported_modules(tree: ast.AST) -> list[str]:
    """Every module an AST of a ``repro.models`` module imports, at top
    level or inside functions, relative imports resolved."""
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                package = ["repro", "models"][:3 - node.level]
                base = ".".join(package + ([base] if base else []))
            names.append(base)
            names += [f"{base}.{alias.name}" for alias in node.names]
    return names


@pytest.mark.parametrize("module", MODEL_MODULES)
def test_model_modules_import_no_tuner(module):
    """The model layer builds programs from its ModelConfig alone: no
    module under repro.models imports the tuner or anything above it."""
    tree = ast.parse((MODELS_DIR / f"{module}.py").read_text())
    upward = [name for name in _imported_modules(tree)
              if any(name == layer or name.startswith(layer + ".")
                     for layer in UPPER_LAYERS)]
    assert upward == [], f"repro.models.{module} imports {upward}"
