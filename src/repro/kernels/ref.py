"""Pure-jnp oracles for the delayed-decode kernel (its correctness
contract) and the table packers it shares with :mod:`.ops`.

These mirror the numpy host codecs in :mod:`repro.core` but stay inside jnp
so they can be jit-compiled and compared against kernel outputs on any
backend.  Tests sweep shapes and assert exact equality between the kernel
(:mod:`.delayed_decode`) and these references.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

TOTAL_BITS = 16
TOTAL = 1 << TOTAL_BITS


def pack_tables(coder) -> Tuple[jnp.ndarray, int]:
    """Bucket-major decode table of a DiscreteCoder: [M, 7] float32.

    Columns: threshold, sym_u, sym_v, ja, jb, k_u, k_v.  All magnitudes are
    < 2**18, hence exactly representable in float32 (MXU-friendly one-hot
    matmul lookups).
    """
    import numpy as np
    t = coder.tables
    k_u = t.k_of[t.sym_u].astype(np.int64)
    k_v = t.k_of[t.sym_v].astype(np.int64)
    tab = np.stack(
        [t.threshold.astype(np.int64), t.sym_u, t.sym_v, t.ja, t.jb, k_u, k_v], axis=1
    ).astype(np.float32)
    return jnp.asarray(tab), int(t.m_bits)


def uniform_m_bits(G: int) -> int:
    """Bucket bits of a G-ary uniform coder's table: ``ceil(log2 G)``."""
    return (int(G) - 1).bit_length() if G > 1 else 0


def pack_tables_uniform(coder) -> Tuple[jnp.ndarray, int]:
    """Bucket-major decode table of a UniformCoder in the same [M, 7] layout.

    The uniform coder's segments are contiguous: symbol ``j`` owns
    ``[ceil(j*2^16/G), ceil((j+1)*2^16/G))``.  With ``m = ceil(log2 G)`` the
    bucket width ``W = 2^(16-m)`` is <= the minimum segment length, so every
    bucket intersects at most two segments — the one owning the bucket's
    first code and (possibly) its successor — which is exactly the
    (threshold, sym_u, sym_v) split the delayed-decode kernel consumes.
    """
    import numpy as np
    G = int(coder.G)
    m = uniform_m_bits(G)
    M = 1 << m
    W = TOTAL >> m
    tab = np.zeros((M, 7), np.float32)
    for p in range(M):
        c0 = p * W
        j0 = (c0 * G) >> TOTAL_BITS
        lo0 = -((-j0 * TOTAL) // G)            # ceil(j0 * 2^16 / G)
        b = -((-(j0 + 1) * TOTAL) // G)        # start of segment j0+1
        if b >= c0 + W:                        # bucket entirely inside j0
            tab[p] = (0, j0, j0, lo0, lo0, b - lo0, b - lo0)
        else:                                  # boundary b interior: two syms
            b2 = -((-(j0 + 2) * TOTAL) // G)
            tab[p] = (b - c0, j0, j0 + 1, lo0, b, b - lo0, b2 - b)
    return jnp.asarray(tab), m


def alias_decode_ref(
    codes: jax.Array, table: jax.Array, m_bits: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """codes int32[N] -> (sym, a, k) int32 — Algorithm 6 / Inv-Translate."""
    codes = codes.astype(jnp.int32)
    shift = TOTAL_BITS - m_bits
    p = codes >> shift
    low = codes & ((1 << shift) - 1)
    row = table[p]  # gather in the reference; one-hot matmul in the kernel
    hit = low < row[:, 0].astype(jnp.int32)
    sym = jnp.where(hit, row[:, 1], row[:, 2]).astype(jnp.int32)
    a = codes - jnp.where(hit, row[:, 3], row[:, 4]).astype(jnp.int32)
    k = jnp.where(hit, row[:, 5], row[:, 6]).astype(jnp.int32)
    return sym, a, k


def delayed_decode_ref(
    codes_dense: jax.Array, tables: jax.Array, m_bits: Tuple[int, ...]
) -> jax.Array:
    """Batched delayed decoding (Algorithm 5), division-free uint32 math.

    codes_dense: int32[T, S] physical codes, left-justified per tuple.
    tables: float32[R, 7 * 128] packed slot tables (``ops.pack_slot_tables``).
    Returns syms int32[T, S].
    """
    from .delayed_decode import LANES, N_FIELDS, slot_layout

    T, S = codes_dense.shape
    layout, _ = slot_layout(tuple(m_bits))
    fields = tables.reshape(tables.shape[0], N_FIELDS, LANES).transpose(0, 2, 1)
    v_info = jnp.zeros((T,), jnp.uint32)
    v_size = jnp.ones((T,), jnp.uint32)
    pending = jnp.zeros((T,), bool)
    pend_code = jnp.zeros((T,), jnp.int32)
    cursor = jnp.zeros((T,), jnp.int32)
    out = []
    lam = jnp.uint32(TOTAL)
    for s in range(S):
        stream = jnp.take_along_axis(codes_dense, cursor[:, None], axis=1)[:, 0]
        code = jnp.where(pending, pend_code, stream)
        cursor = cursor + jnp.where(pending, 0, 1)
        off, n = layout[s]
        slot_tab = fields[off:off + n].reshape(n * LANES, N_FIELDS)
        sym, a, k = alias_decode_ref(code, slot_tab, m_bits[s])
        out.append(sym)
        ku = k.astype(jnp.uint32)
        v_info = v_info * ku + a.astype(jnp.uint32)   # exact: result < 2**32
        v_size = v_size * ku
        pending = v_size >= lam
        pend_code = (v_info & jnp.uint32(0xFFFF)).astype(jnp.int32)
        v_info = jnp.where(pending, v_info >> 16, v_info)
        v_size = jnp.where(pending, v_size >> 16, v_size)
    return jnp.stack(out, axis=1)
