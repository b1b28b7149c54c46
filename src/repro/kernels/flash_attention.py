"""Pallas TPU flash attention (forward): tiled online-softmax.

TPU-native design (DESIGN.md §6):
* grid = (batch*heads, q_blocks, kv_blocks); the LAST grid axis is
  sequential on TPU, so the same (bh, iq) output block is revisited
  across kv blocks with running (m, l, acc) state in VMEM scratch —
  the canonical revisiting-accumulator pattern;
* BlockSpecs keep one q tile [block_q, d] VMEM-resident while K/V tiles
  [block_k, d] stream from HBM: traffic O(S*d) instead of the O(S^2)
  score matrix;
* tile shapes default to 128 (MXU-aligned; d=head_dim is a multiple of
  8 lanes after padding in ops.py);
* GQA without materializing repeated KV heads: the K/V index maps fold
  the query head onto its kv head (h // group);
* causal + sliding-window masks are applied per-tile from iota position
  grids; fully-masked tiles skip the matmul via ``pl.when``.

Validated in ``interpret=True`` mode against ``ref.py`` over shape/dtype
sweeps (tests/test_kernels.py). Forward-only: training uses the pure-JAX
chunked path in models/layers.py; this kernel serves prefill/decode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, block_q: int, block_k: int, nk: int,
                 causal: bool, window: int | None, kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # skip tiles strictly above the causal diagonal
    run = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(run)
    def _tile():
        q = q_ref[0].astype(jnp.float32)             # [bq, d]
        k = k_ref[0].astype(jnp.float32)             # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = k_pos < kv_len
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= (q_pos - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k",
                     "interpret", "kv_len"))
def flash_attention_bhsd(q, k, v, *, interpret: bool, causal: bool = True,
                         window: int | None = None, block_q: int = 128,
                         block_k: int = 128, kv_len: int | None = None):
    """q: [B, H, Sq, d]; k/v: [B, Hkv, Sk, d] -> [B, H, Sq, d].

    Sq/Sk must be padded to block multiples (ops.py handles padding).
    ``interpret`` has no default: on a TPU a caller that forgot it would
    silently run the Python interpreter instead of the Mosaic kernel.
    """
    B, H, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    kv_len = Sk if kv_len is None else kv_len
    nq = Sq // block_q
    nk = Sk // block_k
    scale = 1.0 / math.sqrt(d)
    qf = q.reshape(B * H, Sq, d)
    kf = k.reshape(B * Hkv, Sk, d)
    vf = v.reshape(B * Hkv, Sk, d)

    def kv_index(bh, iq, ik):
        # query head bh = b*H + h attends kv head b*Hkv + h//G
        return (bh // H) * Hkv + (bh % H) // G, ik, 0

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        nk=nk, causal=causal, window=window, kv_len=kv_len)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, d)
