"""Pallas TPU flash attention (causal GQA, sliding window, softcap).

TPU-native tiling: queries stream through VMEM in ``block_q`` x ``hd``
tiles aligned to the MXU (block sizes multiples of 128 on hardware); K/V
rows for the (batch, kv-head) stay resident in VMEM and the kv dimension
is walked with an online-softmax fori_loop (running max m, normalizer l,
accumulator acc — the classic flash recurrence, fp32 accumulation).

Grid: (B * H, Sq / block_q).  GQA maps query head h to kv head h // G in
the BlockSpec index maps — no materialized head repetition.

Validated in interpret mode on CPU against kernels/ref.py over a
shape/dtype sweep (tests/test_kernels.py); no model path calls it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, seq_len, causal, window,
    softcap, sm_scale,
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [block_q, hd]
    hd = q.shape[-1]
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, hd), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    n_k = seq_len // block_k
    if causal:  # only kv blocks up to the diagonal contribute
        n_k = jnp.minimum(n_k, ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k.astype(jnp.float32).T)  # [block_q, block_k]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, bool)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[:, None] + jnp.dot(p, v.astype(jnp.float32))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    sm_scale = hd**-0.5

    # head-major layout: [B*H, S, hd] queries; [B*K, S, hd] keys/values
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, S, hd)

    grid = (B * H, S // block_q)

    def q_map(bh, qi):
        return (bh, qi, 0)

    def kv_map(bh, qi):
        b = bh // H
        h = bh % H
        return (b * K + h // G, 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            block_q=block_q, block_k=block_k, seq_len=S, causal=causal,
            window=window, softcap=softcap, sm_scale=sm_scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, S, hd), kv_map),
            pl.BlockSpec((1, S, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
