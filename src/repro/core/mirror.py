"""HBM mirror of a table's code arena (DESIGN.md §2.8).

The decode kernel's input is gathered on the chip (``gather_runs`` in
:mod:`repro.kernels.delayed_decode`) from a uint16 copy of the arena that
stays in device memory, so a decode ships only each row's start and
length.  The host arena changes in two ways only, and the mirror follows
each:

* appends write at ``used`` (inserts, merges, fault-in re-appends): the
  mirror keeps a watermark ``synced`` and, before the next kernel decode,
  writes ``arena[synced:used]`` in place in fixed-size chunks;
* a rewrite or a restore replaces the arena: the table bumps its
  generation, and the next decode re-uploads the whole arena.

A decode never reads a mirror of another generation, or one that stops
short of the offsets it is handed: :meth:`ArenaMirror.sync` runs before
every kernel decode and settles both.  The mirror holds only what the host
arena holds, so a spilled block is never on the device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np

from repro import telemetry

# Codes per upload step: every append and re-upload writes whole chunks of
# this size (or of the capacity, when smaller), so one program per
# capacity serves every write.
CHUNK = 1 << 18

_C_UPLOAD_BYTES = telemetry.counter("repro.decode.mirror.upload_bytes")
_C_REBUILDS = telemetry.counter("repro.decode.mirror.rebuilds")


def pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def mirror_capacity(need: int, current: int) -> int:
    """Codes the mirror holds: the current capacity while ``need`` fits,
    else the power of two at or above twice ``need``.

    Capacity never shrinks, so a rewrite that trims the host arena keeps
    the compiled programs of every decode bucket; the doubling headroom
    lets a table grow to twice its size before the next step.
    """
    if need <= current:
        return current
    return pow2_at_least(2 * need)


@functools.lru_cache(maxsize=None)
def _chunk_writer() -> Any:
    import jax

    def write(codes: Any, chunk: Any, at: Any) -> Any:
        return jax.lax.dynamic_update_slice(codes, chunk, (at,))

    return jax.jit(write, donate_argnums=0)


def upload_codes(codes: np.ndarray) -> Any:
    """A host code array on the device as uint16, in one copy, padded with
    zeros to a power of two, so each distinct size does not compile a
    program of its own."""
    import jax.numpy as jnp

    codes = np.asarray(codes)
    buf = np.zeros(pow2_at_least(codes.size), np.uint16)
    buf[:codes.size] = codes
    return jnp.asarray(buf)


@dataclasses.dataclass(frozen=True)
class MirroredCodes:
    """Host codes beside a handle on the same codes in HBM.

    ``TablePlan.decode_select`` takes it in place of a host array: the
    numpy path reads ``host``, the kernel path gathers from ``device()``
    (which syncs the mirror) and uploads nothing."""

    host: np.ndarray
    device: Callable[[], Any]


class ArenaMirror:
    """Device copy of one table's ``arena[:used]`` (uint16)."""

    def __init__(self) -> None:
        self.codes: Any = None
        self.capacity = 0
        self.synced = 0
        self.generation = -1

    def sync(self, arena: np.ndarray, used: int, generation: int,
             most: int = 0) -> Any:
        """The mirror of ``arena[:used]`` at ``generation``, sized for an
        arena of at least ``most`` codes."""
        capacity = mirror_capacity(max(used, most), self.capacity)
        if generation != self.generation or capacity != self.capacity:
            import jax.numpy as jnp

            self.codes = None  # free the old copy before the new one
            self.codes = jnp.zeros((capacity,), jnp.uint16)
            self.capacity, self.synced, self.generation = capacity, 0, generation
            _C_REBUILDS.inc()
        if self.synced < used:
            self._write(arena, self.synced, used)
        return self.codes

    def _write(self, arena: np.ndarray, lo: int, hi: int) -> None:
        """Copy ``arena[lo:hi]`` into the mirror, one chunk at a time.  A
        chunk that would run past the capacity starts earlier instead,
        rewriting codes the mirror already holds with the same values."""
        write = _chunk_writer()
        step = min(CHUNK, self.capacity)
        pos = lo
        while pos < hi:
            at = min(pos, self.capacity - step)
            if hi - at >= step:
                chunk = arena[at:at + step]
            else:  # the last chunk: zeros past used
                chunk = np.zeros(step, np.uint16)
                chunk[:hi - at] = arena[at:hi]
            self.codes = write(self.codes, chunk, np.int32(at))
            _C_UPLOAD_BYTES.add(2 * step)
            pos = at + step
        self.synced = hi
