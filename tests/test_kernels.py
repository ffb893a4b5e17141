"""Per-kernel allclose sweeps against the pure-jnp oracles.

Each Pallas kernel (interpret mode) and each jnp program variant is swept
over shapes/dtypes and random tuning points drawn from its own space.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.attention.ops import (
    attention_ref, causal_tiles, decode_attention, flash_attention_jnp,
    flash_attention_pallas)
from repro.kernels.euclid.ops import (
    euclid_pallas, euclid_ref, generate_jnp_variant as euclid_variant,
    make_space as euclid_space, reference_simd, reference_sisd)
from repro.kernels.lintra.ops import (
    generate_jnp_variant as lintra_variant, lintra_pallas, lintra_ref)
from repro.kernels.matmul.ops import matmul_ref, make_space, tuned_matmul

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype=jnp.float32, key=KEY):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------------ matmul
@pytest.mark.parametrize("shape", [(128, 128, 128), (192, 320, 256),
                                   (256, 128, 448), (64, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(shape, dtype):
    M, K, N = shape
    a = rand((M, K), dtype)
    b = rand((K, N), dtype, jax.random.PRNGKey(1))
    ref = matmul_ref(a, b)
    for pt in [
        dict(block_m=64, block_n=128, block_k=128, unroll=1, order="mn",
             scratch=1, lookahead=0),
        dict(block_m=128, block_n=128, block_k=128, unroll=2, order="nm",
             scratch=0, lookahead=1),
        dict(block_m=64, block_n=128, block_k=256, unroll=4, order="mn",
             scratch=1, lookahead=2),
    ]:
        out = tuned_matmul(a, b, point=pt)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@settings(max_examples=12, deadline=None)
@given(idx=st.integers(0, 10**6))
def test_matmul_random_valid_points(idx):
    M, K, N = 192, 256, 256
    space = make_space(M, N, K)
    pts = list(space.iter_valid())
    pt = pts[idx % len(pts)]
    a = rand((M, K))
    b = rand((K, N), key=jax.random.PRNGKey(1))
    np.testing.assert_allclose(
        tuned_matmul(a, b, point=pt), matmul_ref(a, b), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ euclid
@pytest.mark.parametrize("n,m,d", [(128, 32, 32), (250, 90, 70), (64, 64, 128)])
def test_euclid_pallas_and_jnp(n, m, d):
    x = rand((n, d))
    c = rand((m, d), key=jax.random.PRNGKey(2))
    ref = euclid_ref(x, c)
    for pt in [
        dict(block_n=64, block_m=32, block_d=32, unroll=1, vectorize=1,
             order="nm", scratch=1, lookahead=0),
        dict(block_n=128, block_m=32, block_d=16, unroll=2, vectorize=0,
             order="mn", scratch=0, lookahead=1),
    ]:
        if pt["block_d"] > d:
            continue
        np.testing.assert_allclose(
            euclid_pallas(x, c, pt), ref, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(
            euclid_variant(pt, dim=d)(x, c), ref, rtol=1e-3, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(idx=st.integers(0, 10**6))
def test_euclid_random_points_vs_oracle(idx):
    n, m, d = 256, 64, 64
    space = euclid_space(n, m, d)
    pts = list(space.iter_valid())
    pt = pts[idx % len(pts)]
    x = rand((n, d))
    c = rand((m, d), key=jax.random.PRNGKey(2))
    np.testing.assert_allclose(
        euclid_variant(pt, dim=d)(x, c), euclid_ref(x, c),
        rtol=1e-3, atol=1e-3)


def test_euclid_references_agree():
    x = rand((128, 96))
    c = rand((48, 96), key=jax.random.PRNGKey(3))
    np.testing.assert_allclose(
        reference_sisd(96)(x, c), reference_simd(96)(x, c),
        rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------ lintra
@pytest.mark.parametrize("h,w,bands", [(64, 100, 3), (120, 200, 3), (33, 50, 4)])
def test_lintra_variants(h, w, bands):
    img = rand((h, w, bands))
    a = jnp.arange(1.0, bands + 1)
    b = jnp.linspace(-1, 1, bands)
    ref = lintra_ref(img, a, b)
    fold = img.reshape(h, w * bands)
    ab = jnp.stack([jnp.tile(a, w), jnp.tile(b, w)])
    for pt in [
        dict(block_h=8, block_w=128, unroll=1, vectorize=1, order="hw",
             scratch=1, lookahead=0),
        dict(block_h=32, block_w=256, unroll=2, vectorize=0, order="wh",
             scratch=0, lookahead=2),
    ]:
        if pt["block_h"] > h:
            continue
        np.testing.assert_allclose(
            lintra_pallas(fold, ab, pt).reshape(h, w, bands), ref,
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            lintra_variant(pt, bands=bands, width=w)(img, a, b), ref,
            rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- attention
# (B, T, H, Hk, Dh, block_q, block_kv, causal). Ragged T throughout; the
# causal cases run B 1 and 3, G = H // Hk of 1, 7 and 8 (the cells' GQA
# groups), tiles from the prefill tuner's options, and head sizes 32 to
# 128.
FLASH_CASES = [
    (2, 128, 4, 4, 32, 64, 48, True), (2, 128, 4, 4, 32, 64, 48, False),
    (2, 192, 8, 2, 32, 64, 48, True), (2, 192, 8, 2, 32, 64, 48, False),
    (2, 96, 6, 3, 64, 64, 48, True), (2, 96, 6, 3, 64, 64, 48, False),
    (1, 100, 2, 2, 32, 32, 64, True),
    (3, 77, 7, 1, 128, 32, 32, True),
    (1, 130, 16, 2, 128, 64, 128, True),
    (3, 45, 8, 1, 64, 256, 32, True),
    (3, 200, 2, 2, 128, 128, 256, True),
    (1, 70, 7, 1, 32, 64, 32, True),
]


@pytest.mark.parametrize("B,T,H,Hk,Dh,bq,bkv,causal", FLASH_CASES)
def test_flash_attention_vs_ref(B, T, H, Hk, Dh, bq, bkv, causal):
    q = rand((B, T, H, Dh))
    k = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(5))
    ref = attention_ref(q, k, v, causal=causal)
    out = flash_attention_jnp(q, k, v, causal=causal, q_chunk=bq,
                              k_chunk=bkv)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
    outp = flash_attention_pallas(
        q, k, v, dict(block_q=bq, block_kv=bkv), causal=causal)
    np.testing.assert_allclose(outp, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,T,H,Hk,bq,bkv", [(24, 100, 8, 1, 256, 512),
                                               (1, 45, 7, 1, 32, 32)])
def test_flash_attention_bf16_vs_ref(B, T, H, Hk, bq, bkv):
    """bf16 inputs, as served: the kernel's f32 scores and bf16 P·V agree
    with the chunked jnp path's, and both with the float32 reference."""
    Dh = 128
    q = rand((B, T, H, Dh), jnp.bfloat16)
    k = rand((B, T, Hk, Dh), jnp.bfloat16, key=jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), jnp.bfloat16, key=jax.random.PRNGKey(5))
    ref = attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)))
    out = flash_attention_jnp(q, k, v, q_chunk=bq, k_chunk=bkv)
    outp = flash_attention_pallas(q, k, v, dict(block_q=bq, block_kv=bkv))
    assert outp.dtype == jnp.bfloat16
    outp, out = (np.asarray(x, np.float32) for x in (outp, out))
    np.testing.assert_allclose(outp, out, rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(outp, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("T,bq,bkv", [(2844, 256, 512), (100, 32, 64),
                                      (77, 32, 32), (130, 64, 128),
                                      (45, 256, 32), (300, 128, 32)])
def test_causal_tiles_are_those_meeting_the_lower_triangle(T, bq, bkv):
    bq, bkv = min(bq, T), min(bkv, T)
    n_q, n_kv = -(-T // bq), -(-T // bkv)
    # a tile meets the triangle when some row at or after its first key
    # lies in its q block: its first key <= its last real query
    meets = sum(1 for iq in range(n_q) for ik in range(n_kv)
                if ik * bkv <= min((iq + 1) * bq, T) - 1)
    assert causal_tiles(T, T, bq, bkv) == (n_q * n_kv, meets)
    if (T, bq, bkv) == (2844, 256, 512):
        assert meets == 42          # 30 of the 72 tiles skipped


def test_prefill_attention_lowers_to_the_kernel_for_tpu_only():
    """``self_attention_with_cache`` lowered for a TPU carries the fused
    kernel (a Mosaic custom call); lowered for the CPU it is the chunked
    jnp path, which computes it here; a window keeps the jnp path."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models.model import build_model
    from repro.models.params import init_tree

    cfg = get_config("qwen3-moe-30b-a3b").reduced(d_head=128)
    params = init_tree(build_model(cfg).param_defs(), KEY, cfg.param_dtype)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = rand((2, 40, cfg.d_model))
    pos = jnp.arange(40)[None]

    def lowered(c, platform):
        def f(x, lp):
            return L.self_attention_with_cache(x, lp, c, positions=pos)[0]
        return jax.jit(f).trace(x, lp).lower(
            lowering_platforms=(platform,)).as_text()

    assert "tpu_custom_call" in lowered(cfg, "tpu")
    assert "tpu_custom_call" not in lowered(cfg, "cpu")
    windowed = dataclasses.replace(cfg, window=16)
    assert "tpu_custom_call" not in lowered(windowed, "tpu")
    q, k, v = L.qkv_proj(x, lp, cfg)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)
    o = flash_attention_jnp(q, k, v, causal=True, q_chunk=cfg.attn_q_chunk,
                            k_chunk=cfg.attn_k_chunk)
    got, (k2, v2) = L.self_attention_with_cache(x, lp, cfg, positions=pos)
    np.testing.assert_allclose(got, L.attn_out(o, lp, cfg), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(k2, k)


def test_flash_attention_window_and_offset():
    B, T, H, Hk, Dh = 1, 160, 4, 2, 32
    q = rand((B, 32, H, Dh))
    k = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(5))
    ref = attention_ref(q, k, v, causal=True, q_offset=128, window=64)
    out = flash_attention_jnp(q, k, v, causal=True, q_offset=128, window=64,
                              q_chunk=16, k_chunk=32)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_grad_matches_ref():
    B, T, H, Hk, Dh = 2, 96, 4, 2, 16
    q = rand((B, T, H, Dh))
    k = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(5))
    g1 = jax.grad(lambda q: flash_attention_jnp(
        q, k, v, causal=True, q_chunk=32, k_chunk=32).sum())(q)
    g2 = jax.grad(lambda q: attention_ref(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g1, g2, rtol=5e-3, atol=5e-3)


def test_decode_attention_chunked_vs_ref():
    B, T, H, Hk, Dh = 2, 160, 8, 2, 32
    q = rand((B, 1, H, Dh))
    k = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), key=jax.random.PRNGKey(5))
    for length in (64, 100, 160):
        ref = attention_ref(q, k[:, :length], v[:, :length], causal=False)
        out = decode_attention(q, k, v, length=length, k_chunk=32)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_attention_bf16_stability():
    B, T, H, Hk, Dh = 2, 128, 4, 2, 32
    q = rand((B, T, H, Dh), jnp.bfloat16)
    k = rand((B, T, Hk, Dh), jnp.bfloat16, jax.random.PRNGKey(4))
    v = rand((B, T, Hk, Dh), jnp.bfloat16, jax.random.PRNGKey(5))
    out = flash_attention_jnp(q, k, v, causal=True, q_chunk=64, k_chunk=64)
    assert out.dtype == jnp.bfloat16
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("n,d", [(64, 128), (100, 256), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_vs_ref(n, d, dtype):
    from repro.kernels.rmsnorm.ops import rmsnorm_pallas, rmsnorm_ref
    x = rand((n, d), dtype)
    w = rand((d,), jnp.float32, jax.random.PRNGKey(9))
    ref = rmsnorm_ref(x, w)
    for rows in (8, 32, 128):
        out = rmsnorm_pallas(x, w, dict(block_rows=rows))
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32),
            rtol=tol, atol=tol)


def test_rmsnorm_profiles_prefer_larger_rows_when_lean():
    from repro.core import TwoPhaseExplorer
    from repro.core.profiles import SI_L1, TI_F3
    from repro.kernels.rmsnorm.ops import make_rmsnorm_compilette
    comp = make_rmsnorm_compilette(4096, 4096)
    for prof in (SI_L1, TI_F3):
        ex = TwoPhaseExplorer(comp.space)
        pt, sc = ex.run_to_completion(lambda p: comp.simulate(p, prof))
        assert pt is not None and sc < float("inf")
