"""Pallas TPU kernel: blocked flash attention (causal / GQA / sliding window).

The LM substrate's prefill and training hot spot.  Online-softmax tiling:
grid = (batch, q_heads, q_blocks, k_blocks) with the k axis innermost and
"arbitrary" semantics; running max / normalizer / output accumulate in VMEM
scratch across k steps, so the (sq x sk) score matrix never exists in HBM.

GQA is handled in the index map: query head h reads KV head h // group_size,
so KV tiles are fetched once per group rather than replicated.

Causal and sliding-window block skipping: fully-masked (q_block, k_block)
tiles are skipped via pl.when, which on TPU elides both the MXU work and the
KV fetch — for sliding-window layers (Gemma-3 locals) this makes the cost
O(sq * window) instead of O(sq * sk).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Right-aligned positions: query row r has global key-position
    # (sk - sq) + qi*bq + r, which supports prefill with a prefix cache.
    q_off = (sk - sq) + qi * bq
    k_off = ki * bk
    needed = jnp.bool_(True)
    if causal:
        needed &= k_off <= q_off + bq - 1           # block not fully future
    if window is not None:
        needed &= (k_off + bk) > (q_off - window + 1)  # block not fully stale

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, 0]                          # (bq,)
        l_prev = l_ref[...][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(jnp.isneginf(m_cur)[:, None], 0.0,
                      jnp.exp(s - m_cur[:, None]))
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                          jnp.exp(m_prev - m_cur))
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l_fin = l_ref[...][:, 0]
        denom = jnp.maximum(l_fin, 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Flash attention with GQA.

    q: (b, hq, sq, d); k, v: (b, hkv, sk, d); hq % hkv == 0.
    Returns (b, hq, sq, d) in q.dtype.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    grid = (b, hq, sq // bq, sk // bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=sq, sk=sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running normalizer
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
