"""Pallas TPU kernel: decode attention over int8 semantically-quantized KV.

The compressed-KV integration point (DESIGN.md §3.2): KV pages are stored
int8 with per-(token, kv-head) scales fitted by the numeric semantic model;
this kernel dequantizes page tiles *in VMEM* on access and runs
flash-decoding (online softmax over sequence chunks) — the paper's
"decompress on point access" flow with the tile as the access unit.

Layout: grid over KV-sequence chunks; carry (acc, m, l) in VMEM scratch.
q: [B, H, D]; kq/vq: int8[B, S, K, D]; scales f32[B, S, K].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import device

NEG = -1e30
CHUNK = 512


def _kv_attn_kernel(
    scale_q: float,
    length: int,
    q_ref,
    kq_ref,
    ks_ref,
    vq_ref,
    vs_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
):
    ci = pl.program_id(0)
    nc = pl.num_programs(0)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale_q     # [B, K, G, D]
    kq = kq_ref[...].astype(jnp.float32)             # [B, C, K, D]
    ks = ks_ref[...]                                 # [B, C, K]
    vq = vq_ref[...].astype(jnp.float32)
    vs = vs_ref[...]
    B, C, K, D = kq.shape

    kf = kq * ks[..., None]
    vf = vq * vs[..., None]
    s = jnp.einsum("bkgd,bckd->bkgc", q, kf)          # [B, K, G, C]
    pos = ci * C + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, C), 3)
    s = jnp.where(pos < length, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum("bkgc,bckd->bkgd", p, vf)
    m_ref[...] = m_new

    @pl.when(ci == nc - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None])


@ functools.partial(jax.jit, static_argnames= ("length_static", "chunk"))
def kv_attention_int8(
    q: jax.Array,
    kq: jax.Array,
    ks: jax.Array,
    vq: jax.Array,
    vs: jax.Array,
    length_static: int,
    chunk: int = CHUNK,
) -> jax.Array:
    """Flash-decoding over int8 KV. Returns [B, H, D] float32.

    q: [B, H, D]; kq/vq: int8[B, S, K, D]; ks/vs: f32[B, S, K];
    length_static: number of valid cache entries (static for the dry-run
    tile schedule; masking handles the tail).
    """
    B, H, D = q.shape
    _, S, K, _ = kq.shape
    G = H // K
    nc = -(-S // chunk)
    qr = q.reshape(B, K, G, D)

    out = pl.pallas_call(
        functools.partial(_kv_attn_kernel, D ** -0.5, length_static),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((B, K, G, D), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((B, chunk, K, D), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((B, chunk, K), lambda i: (0, i, 0)),
            pl.BlockSpec((B, chunk, K, D), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((B, chunk, K), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((B, K, G, D), lambda i: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((B, K, G, D), jnp.float32),   # acc
            pltpu.VMEM((B, K, G), jnp.float32),      # running max
            pltpu.VMEM((B, K, G), jnp.float32),      # running denom
        ],
        interpret=device.interpret_default(),
    )(qr, kq, ks, vq, vs)
    return out.reshape(B, H, D)
