"""Pallas TPU kernel: fused prefill attention (flash-attention schedule).

The §Perf cell-3 structural fix: the XLA chunked attention round-trips
S²-sized score/probability chunks through HBM (~24 B per score element
measured); this kernel keeps the (q-tile × kv-chunk) score tile in VMEM so
per-layer attention HBM traffic collapses to the q/k/v/o IO.

Grid: (q_tiles, kv_chunks) with the kv dimension innermost; online-softmax
accumulators live in VMEM scratch and the output tile is emitted on the
last kv step.  Causal and sliding-window masks come from position
arithmetic.  GQA layout: q [B, Sq, K, G, D], k/v [B, Sk, K, D].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import device

NEG = -1e30


def _flash_kernel(
    scale: float,
    causal: bool,
    window: int,
    sq: int,
    sk: int,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
):
    qi = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale      # [B, qb, K, G, D]
    k = k_ref[...].astype(jnp.float32)              # [B, kc, K, D]
    v = v_ref[...].astype(jnp.float32)
    B, qb, K, G, D = q.shape
    kc = k.shape[1]

    s = jnp.einsum("bqkgd,bckd->bqkgc", q, k)       # VMEM-resident tile
    q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kc), 0)
    k_pos = kj * kc + jax.lax.broadcasted_iota(jnp.int32, (qb, kc), 1)
    mask = k_pos < sk
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, :, None, None, :], s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[None, :, None, None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "bqkgc,bckd->bqkgd", p, v
    )
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(
            o_ref.dtype
        )


@ functools.partial(
    jax.jit, static_argnames= ("causal", "window", "q_block", "kv_chunk")
)
def flash_prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_chunk: int = 512,
) -> jax.Array:
    """q: [B, Sq, H, D]; k/v: [B, Sk, K, D].  Returns [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    qb = min(q_block, Sq)
    kc = min(kv_chunk, Sk)
    nq, nk = -(-Sq // qb), -(-Sk // kc)
    qr = jnp.pad(
        q.reshape(B, Sq, K, G, D), ((0, 0), (0, nq * qb - Sq), (0, 0), (0, 0), (0, 0))
    )
    kr = jnp.pad(k, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))
    vr = jnp.pad(v, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_flash_kernel, D ** -0.5, causal, window, Sq, Sk),
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((B, qb, K, G, D), lambda i, j: (0, i, 0, 0, 0)),
            pl.BlockSpec((B, kc, K, D), lambda i, j: (0, j, 0, 0)),
            pl.BlockSpec((B, kc, K, D), lambda i, j: (0, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, qb, K, G, D), lambda i, j: (0, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq * qb, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((B, qb, K, G, D), jnp.float32),
            pltpu.VMEM((B, qb, K, G), jnp.float32),
            pltpu.VMEM((B, qb, K, G), jnp.float32),
        ],
        interpret=device.interpret_default(),
    )(qr, kr, vr)
    return out[:, :Sq].reshape(B, Sq, H, D)
