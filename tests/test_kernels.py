"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles.

Sweeps shapes/dtypes with hypothesis; every kernel must match ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models.attention import chunked_attention
from repro.sharding.policy import EmbeddingPlan


def embedding_bag(table, idx, w=None, *, combiner="sum", interpret=False):
    """Single-table bag through the public plan API (ex-legacy module)."""
    return ops.embedding_bag(table, idx, w,
                             plan=EmbeddingPlan(combiner=combiner),
                             impl="interpret" if interpret else None)

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 9),
    n=st.integers(1, 7),
    R=st.integers(4, 80),
    D=st.sampled_from([4, 8, 16, 32]),
    comb=st.sampled_from(["sum", "mean", "max"]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_embedding_bag_sweep(B, n, R, D, comb, dtype):
    key = jax.random.PRNGKey(B * 1000 + n * 100 + R)
    table = jax.random.normal(key, (R, D), dtype=jnp.float32).astype(dtype)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (B, n), 0, R)
    out = embedding_bag(table, idx, combiner=comb, interpret=True)
    expect = ref.embedding_bag_ref(table, idx, combiner=comb)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_embedding_bag_weighted():
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (50, 16))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (6, 4), 0, 50)
    w = jax.random.uniform(jax.random.fold_in(key, 2), (6, 4))
    out = embedding_bag(table, idx, w, combiner="sum", interpret=True)
    expect = ref.embedding_bag_ref(table, idx, w, combiner="sum")
    np.testing.assert_allclose(out, expect, atol=1e-5, rtol=1e-5)


def test_embedding_bag_repeated_indices():
    table = jnp.eye(8, 8)
    idx = jnp.array([[3, 3, 3]])
    out = embedding_bag(table, idx, combiner="sum", interpret=True)
    assert float(out[0, 3]) == 3.0


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@settings(max_examples=16, deadline=None)
@given(
    B=st.integers(1, 3),
    Sq=st.sampled_from([8, 24, 64]),
    Hkv=st.sampled_from([1, 2]),
    G=st.sampled_from([1, 2, 4]),
    Dh=st.sampled_from([16, 32, 64]),
    causal=st.booleans(),
    window=st.sampled_from([None, 8, 32]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_sweep(B, Sq, Hkv, G, Dh, causal, window, dtype):
    key = jax.random.PRNGKey(Sq * 10 + Hkv)
    Hq = Hkv * G
    q = jax.random.normal(key, (B, Sq, Hq, Dh)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, Hkv, Dh)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, Hkv, Dh)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_k=16, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=_tol(dtype) * 4, rtol=_tol(dtype) * 4)


def test_flash_attention_softcap():
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (2, 32, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 32, 2, 32))
    out = flash_attention(q, k, v, causal=True, softcap=20.0,
                          block_q=8, block_k=8, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_flash_attention_nonmultiple_blocks():
    """seq not divisible by block size exercises padding + kv_len masking."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 35, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 35, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 35, 2, 16))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# xla chunked attention (the dry-run lowering path) vs oracle
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(
    Sq=st.sampled_from([16, 48, 128]),
    G=st.sampled_from([1, 3]),
    causal=st.booleans(),
    window=st.sampled_from([None, 16]),
    q_chunk=st.sampled_from([8, 16, 64]),
)
def test_chunked_attention_sweep(Sq, G, causal, window, q_chunk):
    if window is not None:
        causal = True     # sliding windows are causal in every arch we serve
    key = jax.random.PRNGKey(Sq + G)
    B, Hkv, Dh = 2, 2, 16
    q = jax.random.normal(key, (B, Sq, Hkv * G, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, Hkv, Dh))
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_chunk=q_chunk, k_chunk=q_chunk)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------
@settings(max_examples=16, deadline=None)
@given(
    B=st.integers(1, 3),
    L=st.sampled_from([16, 48, 100]),
    Hkv=st.sampled_from([1, 2]),
    G=st.sampled_from([1, 4]),
    window=st.sampled_from([None, 8]),
    valid_frac=st.floats(0.2, 1.0),
)
def test_decode_attention_sweep(B, L, Hkv, G, window, valid_frac):
    key = jax.random.PRNGKey(L + Hkv)
    Hq, Dh = Hkv * G, 32
    kc = jax.random.normal(key, (B, L, Hkv, Dh))
    vc = jax.random.normal(jax.random.fold_in(key, 1), (B, L, Hkv, Dh))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, Hq, Dh))
    n_valid = max(1, int(L * valid_frac))
    cache_pos = jnp.broadcast_to(jnp.arange(L), (B, L))
    cache_pos = jnp.where(cache_pos < n_valid, cache_pos, -1).astype(jnp.int32)
    pos = jnp.full((B,), n_valid - 1, jnp.int32)
    out = decode_attention(q, kc, vc, cache_pos, pos, window=window,
                           block_k=16, interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, cache_pos, pos, window=window)
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=3e-5)


def test_decode_attention_ring_wrap():
    """Ring-buffer cache positions (wrapped writes) mask correctly."""
    key = jax.random.PRNGKey(9)
    B, L, Hkv, G, Dh = 2, 24, 2, 2, 16
    kc = jax.random.normal(key, (B, L, Hkv, Dh))
    vc = jax.random.normal(jax.random.fold_in(key, 1), (B, L, Hkv, Dh))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, Hkv * G, Dh))
    base = jnp.arange(L)
    cache_pos = jnp.stack([jnp.where(base < 8, base + L, base)] * B).astype(jnp.int32)
    pos = jnp.full((B,), L + 7, jnp.int32)
    out = decode_attention(q, kc, vc, cache_pos, pos, window=12, block_k=8,
                           interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, cache_pos, pos, window=12)
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# implementation selection
# ---------------------------------------------------------------------------
def test_kernel_impl_follows_backend(monkeypatch):
    """No impl= on CPU runs the XLA path; on a TPU backend, the kernels."""
    assert jax.default_backend() == "cpu"
    assert ops.get_default_impl() == "xla"
    assert ops.resolve_impl(None) == "xla"
    assert ops.resolve_impl("interpret") == "interpret"
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    assert ops.get_default_impl() == "pallas"
    assert ops.resolve_impl(None) == "pallas"


def test_pallas_requested_off_tpu_raises():
    table = jnp.ones((16, 8), jnp.float32)
    idx = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        ops.embedding_bag(table, idx, plan=EmbeddingPlan(), impl="pallas")
    with pytest.raises(ValueError, match="unknown kernel impl"):
        ops.resolve_impl("cuda")


def test_pinned_impl_is_used_and_reset():
    try:
        ops.set_default_impl("interpret")
        assert ops.resolve_impl(None) == "interpret"
    finally:
        ops.set_default_impl(None)
    assert ops.get_default_impl() == "xla"
