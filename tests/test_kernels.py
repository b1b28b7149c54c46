"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ops import flash_attention as flash_model_layout
from repro.kernels.ref import attention_ref
from repro.models.layers import chunked_attention, dense_attention


def _make(B, H, Hkv, Sq, Sk, d, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = (jax.random.normal(ks[0], (B, H, Sq, d), jnp.float32)).astype(dtype)
    k = (jax.random.normal(ks[1], (B, Hkv, Sk, d), jnp.float32)).astype(dtype)
    v = (jax.random.normal(ks[2], (B, Hkv, Sk, d), jnp.float32)).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,d", [
    (1, 2, 2, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA g=2
    (1, 8, 2, 128, 128),    # GQA g=4, wide head
    (2, 2, 1, 192, 32),     # MQA, non-pow2 seq
])
def test_flash_vs_ref_shapes(B, H, Hkv, S, d, dtype):
    q, k, v = _make(B, H, Hkv, S, S, d, dtype)
    out = flash_attention_bhsd(q, k, v, interpret=True, causal=True,
                               block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True)
    err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
    assert float(err) < TOL[dtype], f"err {err}"


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_sliding_window(window):
    q, k, v = _make(1, 4, 2, 256, 256, 64, jnp.float32)
    out = flash_attention_bhsd(q, k, v, interpret=True, causal=True,
                               window=window, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True, window=window)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_flash_non_causal():
    q, k, v = _make(1, 2, 2, 128, 128, 64, jnp.float32)
    out = flash_attention_bhsd(q, k, v, interpret=True, causal=False,
                               block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=False)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_model_layout_wrapper_pads_ragged_seq():
    # S=100 not a block multiple: ops.py pads and un-pads
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 100, 4, 64))
    k = jax.random.normal(ks[1], (2, 100, 2, 64))
    v = jax.random.normal(ks[2], (2, 100, 2, 64))
    out = flash_model_layout(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    assert out.shape == q.shape
    assert float(jnp.abs(out - ref).max()) < 2e-5


@settings(max_examples=12, deadline=None)
@given(
    S=st.sampled_from([64, 128, 192, 320]),
    d=st.sampled_from([32, 64, 128]),
    H=st.sampled_from([2, 4]),
    G=st.sampled_from([1, 2]),
    causal=st.booleans(),
)
def test_flash_property_sweep(S, d, H, G, causal):
    """Property: kernel == oracle across random shape combinations."""
    Hkv = max(H // G, 1)
    q, k, v = _make(1, H, Hkv, S, S, d, jnp.float32, seed=S + d)
    out = flash_attention_bhsd(q, k, v, interpret=True, causal=causal,
                               block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 3e-5


# ---------------------------------------------------------------------------
# the pure-JAX chunked path (training) against the dense reference
@pytest.mark.parametrize("S,cq,ckv", [(96, 32, 32), (256, 64, 128),
                                      (130, 64, 64)])
def test_chunked_attention_vs_dense(S, cq, ckv):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 32))
    k = jax.random.normal(ks[1], (2, S, 2, 32))
    v = jax.random.normal(ks[2], (2, S, 2, 32))
    out = chunked_attention(q, k, v, causal=True, chunk_q=cq, chunk_kv=ckv)
    ref = dense_attention(q, k, v, causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_chunked_attention_window_and_grad():
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    out = chunked_attention(q, k, v, causal=True, window=32, chunk_q=32,
                            chunk_kv=32)
    ref = dense_attention(q, k, v, causal=True, window=32)
    assert float(jnp.abs(out - ref).max()) < 2e-5
    # differentiable (training path) — dense ref comparison of grads
    f = lambda qq: chunked_attention(qq, k, v, causal=True, chunk_q=32,  # noqa: E731
                                     chunk_kv=32).sum()
    g = lambda qq: dense_attention(qq, k, v, causal=True).sum()  # noqa: E731
    gc = jax.grad(f)(q)
    gd = jax.grad(g)(q)
    assert float(jnp.abs(gc - gd).max()) < 5e-5
