"""Jit'd public wrappers around the Pallas kernels.

Interpret or compile is the platform's choice (:mod:`repro.device`): the
kernels compile for the chip on a TPU and run in the Pallas interpreter on
the CPU.  Helpers convert host-side coder objects into the dense device
table layout.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.coders import DiscreteCoder, UniformCoder
from . import ref as ref_lib
from .alias_decode import alias_decode
from .delayed_decode import LANES, N_FIELDS, delayed_decode, slot_layout
from .flash_prefill import flash_prefill_attention
from .kv_attention import kv_attention_int8

__all__ = [
    "alias_decode",
    "delayed_decode",
    "kv_attention_int8",
    "flash_prefill_attention",
    "pack_slot_tables",
    "dense_codes",
]


def pack_slot_tables(coders: Sequence) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    """Pack per-slot decode tables into the kernel's [R, 7 * 128] layout.

    Accepts a mix of :class:`DiscreteCoder` (alias layout, Appendix C) and
    :class:`UniformCoder` (contiguous segments) — both lower to the same
    bucket-major (threshold, sym_u, sym_v, ja, jb, k_u, k_v) row format the
    delayed-decode kernel consumes.  Each slot keeps its own bucket count:
    bucket ``p`` of slot ``s`` lands at row ``offset_s + (p >> 7)``, lane
    ``field * 128 + (p & 127)`` (:func:`~.delayed_decode.slot_layout`).
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
