"""Pallas TPU kernel: batched delayed-coding decode (Algorithm 5).

The paper's CPU decoder is a scalar loop; the TPU restructuring
(DESIGN.md §2) observes the virtual-bits chain is sequential only *within*
a tuple, so a VMEM tile holds a block of tuples and the kernel unrolls the
slot chain across the whole tile:

* the mixed-radix accumulator update ``V_info = V_info*k + a`` needs no
  division and stays < 2**32 (paper §5.1 invariant), so uint32 lane
  arithmetic is *exact*;
* per-slot alias-table lookups split the bucket index ``p`` into a row
  ``p >> 7`` and a lane ``p & 127``: a one-hot × table matmul (MXU, pinned
  to fp32 contract precision so table values up to 2**16 stay exact)
  picks the row, a one-hot lane reduction picks the bucket.  Slots with
  at most 128 buckets skip the matmul;
* the "read from stream or virtual bits" choice is a select; the stream
  cursor advance is a masked add, and the cursor read is a row-wise
  one-hot reduction (no gathers anywhere).

Inputs are the dense per-tuple layout produced by the host encoder
(``codes_dense[T, S]``, left-justified).  Tables: float32[R, 7 * 128], the
slot blocks laid out by :func:`slot_layout` — each slot at its own bucket
count, so one wide slot does not pad the others.
"""

from __future__ import annotations

import functools
from typing import Set, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import device, telemetry

TOTAL_BITS = 16
LAM = 1 << 16  # python literal; materialized inside the kernel
BLOCK_T = 256
LANES = 128
N_FIELDS = 7  # threshold, sym_u, sym_v, ja, jb, k_u, k_v
SUBLANES = 8

# jit-compile observability (DESIGN.md §9): the first call for a new
# (shape, m_bits) signature traces + compiles; later calls replay.  The
# first-call wall time is attributed to the jit_compile phase (it is
# compile-dominated), cache hits are counted separately.
_SEEN_SIGS: Set[Tuple] = set()
_H_JIT = telemetry.histogram("repro.plan.compile.pallas_jit")
_C_JIT_MISS = telemetry.counter("repro.plan.cache.pallas_miss")
_C_JIT_HIT = telemetry.counter("repro.plan.cache.pallas_hit")


def slot_layout(m_bits: Tuple[int, ...]) -> Tuple[Tuple[Tuple[int, int], ...], int]:
    """``((row offset, row count) per slot, total rows)`` of the packed table.

    Slot ``s`` owns ``2**m_bits[s]`` buckets; bucket ``p`` lives at row
    ``offset + (p >> 7)``, lane ``f * 128 + (p & 127)`` for field ``f``.  A
    slot of at most 128 buckets is one row; wider slots start on a sublane
    tile and are padded to whole tiles (the matmul's contraction dim).
    """
    out = []
    off = 0
    for m in m_bits:
        n = max(1, (1 << m) // LANES)
        if n > 1:
            n = -(-n // SUBLANES) * SUBLANES
            off = -(-off // SUBLANES) * SUBLANES
        out.append((off, n))
        off += n
    return tuple(out), max(SUBLANES, -(-off // SUBLANES) * SUBLANES)


def slot_params(m_bits: Tuple[int, ...]) -> Tuple[Tuple[int, ...], ...]:
    """Per-slot kernel scalars ``(shift, base row, row delta, window)``.

    Slot ``s`` looks its bucket up in the ``window`` table rows starting at
    the sublane-aligned ``base``; its own rows start ``delta`` rows in.
    One-row slots share 8-row tiles, so their window is one tile.
    """
    layout, _ = slot_layout(m_bits)
    out = []
    for m, (off, n) in zip(m_bits, layout):
        base = off - off % SUBLANES
        out.append((TOTAL_BITS - m, base, off - base, max(n, SUBLANES)))
    return tuple(out)


def _delayed_kernel(
    windows: Tuple[int, ...], params_ref, codes_ref, tables_ref, out_ref,
    rows_ref, state_ref,
):
    codes = codes_ref[...]                                  # [BT, S] int32
    BT, S = codes.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    # per-tuple chain state, one [BT, 1] column each: v_info, v_size (uint32
    # bits), pending flag, pending code, stream cursor
    V_INFO, V_SIZE, PENDING, PEND_CODE, CURSOR = range(5)
    state_ref[...] = jnp.zeros(state_ref.shape, jnp.int32)
    state_ref[V_SIZE] = jnp.ones((BT, 1), jnp.int32)
    out_ref[...] = jnp.zeros((BT, S), jnp.int32)

    def slot(s, _):
        shift = params_ref[0, s]
        base = pl.multiple_of(params_ref[1, s], SUBLANES)
        delta = params_ref[2, s]
        window = params_ref[3, s]
        v_info = pltpu.bitcast(state_ref[V_INFO], jnp.uint32)
        v_size = pltpu.bitcast(state_ref[V_SIZE], jnp.uint32)
        pending = state_ref[PENDING] != 0
        cursor = state_ref[CURSOR]

        # stream read: row-wise one-hot reduction against the cursor
        sel = (cursor == cols).astype(jnp.int32)
        stream = jnp.sum(codes * sel, axis=1, keepdims=True)
        code = jnp.where(pending, state_ref[PEND_CODE], stream)
        state_ref[CURSOR] = cursor + jnp.where(pending, 0, 1)

        # alias lookup: the bucket's table row by a one-hot matmul over the
        # slot's row window, then its lane by a one-hot lane reduction
        p = code >> shift
        low = code & ((1 << shift) - 1)
        row = (p >> 7) + delta
        for w in windows:  # one static matmul shape per window size
            @pl.when(window == w)
            def _(w=w):
                hot = (row == jax.lax.broadcasted_iota(
                    jnp.int32, (1, w), 1)).astype(jnp.float32)
                rows_ref[...] = jnp.dot(
                    hot, tables_ref[pl.ds(base, w), :],
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        lo_hot = (p & (LANES - 1)) == lanes                 # [BT, 128]

        def field(f: int) -> jax.Array:
            picked = jnp.where(lo_hot, rows_ref[:, f * LANES:(f + 1) * LANES], 0.0)
            return jnp.sum(picked, axis=1, keepdims=True).astype(jnp.int32)

        hit = low < field(0)
        sym = jnp.where(hit, field(1), field(2))
        a = code - jnp.where(hit, field(3), field(4))
        # f32 -> uint32 has no direct Mosaic cast: go through int32
        k = jnp.where(hit, field(5), field(6)).astype(jnp.uint32)
        out_ref[...] = jnp.where(cols == s, sym, out_ref[...])

        # division-free mixed-radix update (uint32-exact, §5.1)
        v_info = v_info * k + a.astype(jnp.uint32)
        v_size = v_size * k
        pending = v_size >= jnp.uint32(LAM)
        state_ref[PEND_CODE] = (v_info & jnp.uint32(0xFFFF)).astype(jnp.int32)
        state_ref[PENDING] = pending.astype(jnp.int32)
        v_info = jnp.where(pending, v_info >> 16, v_info)
        v_size = jnp.where(pending, v_size >> 16, v_size)
        state_ref[V_INFO] = pltpu.bitcast(v_info, jnp.int32)
        state_ref[V_SIZE] = pltpu.bitcast(v_size, jnp.int32)
        return 0

    jax.lax.fori_loop(0, S, slot, 0)


def delayed_decode(
    codes_dense: jax.Array,
    tables: jax.Array,
    m_bits: Tuple[int, ...],
) -> jax.Array:
    """codes int32[T, S] + packed tables f32[R, 7*128] -> syms int32[T, S].

    Thin telemetry shim over the jitted kernel: counts plan-cache
    hits/misses per trace signature and books first-call (compile) time.
    Interpret or compile is the platform's choice (:mod:`repro.device`).
    """
    interpret = device.interpret_default()
    sig = (codes_dense.shape, tables.shape, tuple(m_bits), interpret)
    if sig in _SEEN_SIGS:
        _C_JIT_HIT.inc()
        return _delayed_decode_jit(codes_dense, tables, m_bits, interpret)
    _SEEN_SIGS.add(sig)
    _C_JIT_MISS.inc()
    t0 = telemetry.clock()
    out = _delayed_decode_jit(codes_dense, tables, m_bits, interpret)
    _H_JIT.observe_since(t0)
    return out


@functools.partial(jax.jit, static_argnames=("m_bits", "interpret"))
def _delayed_decode_jit(
    codes_dense: jax.Array,
    tables: jax.Array,
    m_bits: Tuple[int, ...],
    interpret: bool,
) -> jax.Array:
    T, S = codes_dense.shape
    n_blocks = -(-T // BLOCK_T)
    padded = n_blocks * BLOCK_T
    codes_p = jnp.pad(codes_dense.astype(jnp.int32), ((0, padded - T), (0, 0)))
    params = slot_params(m_bits)
    windows = tuple(sorted({p[3] for p in params}))
    out = pl.pallas_call(
        functools.partial(_delayed_kernel, windows),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK_T, S), lambda i: (i, 0)),
            pl.BlockSpec(tables.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_T, S), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, S), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_T, N_FIELDS * LANES), jnp.float32),
            pltpu.VMEM((5, BLOCK_T, 1), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(params, jnp.int32).T, codes_p, tables)
    return out[:T]
