"""Pallas TPU kernel: batched constant-time Inv-Translate (Algorithm 6).

The alias tables live in VMEM; the bucket lookup is a one-hot × table
matmul (MXU) instead of a gather — the TPU-native formulation of the
paper's "O(1) decode" (DESIGN.md §2).  Table entries are < 2**18 so
float32 matmul accumulation is exact.

Block layout: codes are tiled into (BLOCK,) vectors over a 1-D grid; the
[M, 7] table is broadcast to every tile (it is tiny: M <= 2**m buckets).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import device

TOTAL_BITS = 16
BLOCK = 1024


def _alias_kernel(m_bits: int, codes_ref, table_ref, sym_ref, a_ref, k_ref):
    codes = codes_ref[...]                                   # [BLOCK] int32
    table = table_ref[...]                                   # [M, 7] f32
    M = table.shape[0]
    shift = TOTAL_BITS - m_bits
    p = codes >> shift
    low = codes & ((1 << shift) - 1)
    # one-hot [BLOCK, M] @ [M, 7] -> per-code table row (exact in f32)
    onehot = (p[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
              ).astype(jnp.float32)
    rows = jnp.dot(onehot, table, preferred_element_type=jnp.float32)
    thresh = rows[:, 0].astype(jnp.int32)
    hit = low < thresh
    sym = jnp.where(hit, rows[:, 1], rows[:, 2]).astype(jnp.int32)
    a = codes - jnp.where(hit, rows[:, 3], rows[:, 4]).astype(jnp.int32)
    k = jnp.where(hit, rows[:, 5], rows[:, 6]).astype(jnp.int32)
    sym_ref[...] = sym
    a_ref[...] = a
    k_ref[...] = k


@functools.partial(jax.jit, static_argnames=("m_bits",))
def alias_decode(
    codes: jax.Array, table: jax.Array, m_bits: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """codes int32[N] + table f32[M, 7] -> (sym, a, k) int32[N]."""
    N = codes.shape[0]
    n_blocks = -(-N // BLOCK)
    padded = n_blocks * BLOCK
    codes_p = jnp.pad(codes.astype(jnp.int32), (0, padded - N))
    M = table.shape[0]

    out_shape = [jax.ShapeDtypeStruct((padded,), jnp.int32)] * 3
    grid = (n_blocks,)
    sym, a, k = pl.pallas_call(
        functools.partial(_alias_kernel, m_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((M, 7), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,))] * 3,
        out_shape=out_shape,
        interpret=device.interpret_default(),
    )(codes_p, table)
    return sym[:N], a[:N], k[:N]
