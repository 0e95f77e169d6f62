"""Host-side helpers for the delayed-decode kernel
(:mod:`.delayed_decode`).

They convert host coder objects into the kernel's packed device table
layout (:func:`pack_slot_tables`, :func:`widen_table`,
:func:`coder_m_bits`) and a CSR batch into dense left-justified codes
(:func:`dense_codes`, the input of :func:`.ref.delayed_decode_ref`).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.coders import DiscreteCoder, UniformCoder
from . import ref as ref_lib
from .delayed_decode import LANES, N_FIELDS, TOTAL_BITS, slot_layout

__all__ = [
    "pack_slot_tables",
    "dense_codes",
]


def coder_m_bits(coder) -> int:
    """Bucket bits of a slot coder's kernel table (its own width)."""
    if isinstance(coder, DiscreteCoder):
        return int(coder.tables.m_bits)
    if isinstance(coder, UniformCoder):
        return ref_lib.uniform_m_bits(coder.G)
    raise TypeError(f"cannot pack device tables for {type(coder).__name__}")


def widen_table(tab: np.ndarray, m: int, m_to: int) -> np.ndarray:
    """A slot's ``[2**m, 7]`` decode table re-bucketed at ``2**m_to``
    buckets (``m_to >= m``).

    Each bucket splits into ``2**(m_to - m)`` sub-buckets of width ``w``
    that keep its symbols, offsets and option counts; sub-bucket ``j``
    reads the low bits past ``j * w``, so its threshold moves down by
    ``j * w``, clipped to ``[0, w]``.  Every code decodes as before.
    """
    d = m_to - m
    if d < 0:
        raise ValueError(f"cannot narrow a {m}-bit table to {m_to} bits")
    if d == 0:
        return tab
    w = 1 << (TOTAL_BITS - m_to)
    out = np.repeat(tab, 1 << d, axis=0)
    thr = tab[:, :1] - w * np.arange(1 << d, dtype=np.float32)[None, :]
    out[:, 0] = np.clip(thr, 0, w).reshape(-1)
    return out


def pack_slot_tables(
    coders: Sequence, m_bits: Optional[Sequence[int]] = None
) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    """Pack per-slot decode tables into the kernel's [R, 7 * 128] layout.

    Accepts a mix of :class:`DiscreteCoder` (alias layout, Appendix C) and
    :class:`UniformCoder` (contiguous segments) — both lower to the same
    bucket-major (threshold, sym_u, sym_v, ja, jb, k_u, k_v) row format the
    delayed-decode kernel consumes.  Each slot keeps its own bucket count:
    bucket ``p`` of slot ``s`` lands at row ``offset_s + (p >> 7)``, lane
    ``field * 128 + (p & 127)`` (:func:`~.delayed_decode.slot_layout`).

    ``m_bits`` packs at given slot widths instead: each coder's table is
    widened to its slot's bits (:func:`widen_table`), and slots past the
    last coder hold zeros (their symbols are never read).  Plans that pack
    at the same ``m_bits`` share one compiled kernel.
    """
    tabs: List[np.ndarray] = []
    mbits: List[int] = []
    for c in coders:
        if isinstance(c, DiscreteCoder):
            t, m = ref_lib.pack_tables(c)
        elif isinstance(c, UniformCoder):
            t, m = ref_lib.pack_tables_uniform(c)
        else:
            raise TypeError(f"cannot pack device tables for {type(c).__name__}")
        tabs.append(np.asarray(t))
        mbits.append(m)
    if m_bits is not None:
        if len(m_bits) < len(tabs):
            raise ValueError(f"{len(m_bits)} slot widths for {len(tabs)} coders")
        tabs = [widen_table(t, m, int(mt)) for t, m, mt in zip(tabs, mbits, m_bits)]
        tabs += [np.zeros((1 << int(mt), N_FIELDS), np.float32)
                 for mt in m_bits[len(mbits):]]
        mbits = [int(mt) for mt in m_bits]
    layout, n_rows = slot_layout(tuple(mbits))
    out = np.zeros((n_rows, N_FIELDS, LANES), np.float32)
    for t, (off, _) in zip(tabs, layout):
        M = t.shape[0]
        rows = -(-M // LANES)
        blk = np.zeros((rows * LANES, N_FIELDS), np.float32)
        blk[:M] = t
        out[off:off + rows] = blk.reshape(rows, LANES, N_FIELDS).transpose(0, 2, 1)
    return jnp.asarray(out.reshape(n_rows, N_FIELDS * LANES)), tuple(mbits)


def dense_codes(codes: np.ndarray, offsets: np.ndarray, n_slots: int) -> np.ndarray:
    """CSR (codes, offsets) -> dense [T, S] int32, left-justified."""
    T = offsets.size - 1
    out = np.zeros((T, n_slots), np.int32)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int64)
    cols = np.arange(n_slots)[None, :]
    mask = cols < lens[:, None]
    idx = offsets[:-1, None] + np.minimum(cols, np.maximum(lens[:, None] - 1, 0))
    out = np.where(mask, codes[idx], 0).astype(np.int32)
    return out
