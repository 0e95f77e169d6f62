"""Slot-plan compilation: lowering fitted semantic models to static slots.

This is the compile step of the batched fast path (DESIGN.md §2).  A fitted
:class:`~repro.core.blitzcrank.TableCodec` walks value-at-a-time through
Python models; ``compile_plan`` lowers it — when the schema allows — into a
*slot plan*: a fixed sequence of ``S`` slots per tuple, each owned by a
static :class:`DiscreteCoder`/:class:`UniformCoder` (or a
:class:`~repro.core.vectorized.CondSlot` for conditional columns), plus
vectorized value<->symbol translation tables.  The plan feeds
``vectorized.encode_batch``/``decode_batch``/``decode_select`` and, when all
slots are plain tables, the Pallas ``delayed_decode`` kernel.

Plan-ability rules (DESIGN.md §2.3):

* ``block_tuples == 1`` — multi-tuple blocks chain virtual bits across rows,
  which the tuple-parallel layout cannot reproduce;
* every column model lowers: categorical (1 slot), numeric two-level
  (1 + len(l2) slots), conditional categorical with an earlier categorical
  (or conditional) parent chain (1 CondSlot), and strings (one fixed
  word/delimiter template per plan);
* time-series models are stateful across rows and always fall back.

A string column whose rows come in a few word templates lowers to each of
them, so a table compiles to a small *family* of plans (the cross product
of its columns' templates, modal plan first); a block records which member
encoded it.  Plan-ability is *per schema*; conformance is *per row*: a row
that no member takes (unseen category, out-of-range numeric, a string of
no lowered template) is encoded by the scalar path and its block flagged
slow.  Fast and slow blocks share one code-stream format — every member
emits bit-identical codes to the scalar encoder — so the flag and the
template id only route decoding.
"""

from __future__ import annotations

import contextlib
import itertools
import math
from collections import Counter

import numpy as np
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from repro import sanitize, telemetry

from . import vectorized
from .casts import checked_astype
from .coders import TOTAL, DiscreteCoder, UniformCoder
from .mirror import MirroredCodes, upload_codes
from .models import (
    _DIGIT10,
    CategoricalModel,
    ConditionalCategoricalModel,
    NumericModel,
    StringModel,
    TimeSeriesModel,
    _is_digit_token,
)
from .vectorized import CondSlot

MAX_COND_KEYS = 1 << 16  # cap on enumerated parent-chain combinations

# A string column lowers to its most frequent word templates (word count and
# digit-or-word kind at each position), modal template first.  Templates are
# added until they cover TEMPLATE_COVERAGE of the fit's rows: past that the
# rows left are too few to repay a plan.
TEMPLATE_COVERAGE = 0.999
# At most this many per column: each template present in a read batch costs
# one more decode call, and four cover the word-count spread of a
# variable-length name in a fixed-format string.
MAX_COLUMN_TEMPLATES = 4
# A template seen in fewer of the fit's rows than this is not worth a plan;
# its rows stay on the escape path.
MIN_TEMPLATE_SHARE = 0.001
# Plans per table, the cross product of its columns' templates taken in
# descending joint share: more would add decode calls for rare row shapes.
MAX_FAMILY = 8

# Hot-path metric handles (DESIGN.md §9).  The ``repro.core.encode*`` /
# ``repro.core.decode*`` spans are leaf phases of the wall-time breakdown
# and pallas_pack is a jit-compile event; the ``repro.decode.*`` spans
# split a decode into its stages and belong to no phase.
_C_ENCODE_ROWS = telemetry.counter("repro.core.encode.rows")
_C_DECODE_ROWS = telemetry.counter("repro.core.decode.rows")
_C_KERNEL_ROWS = telemetry.counter("repro.decode.kernel_rows")
_C_KERNEL_ROWS_PADDED = telemetry.counter("repro.decode.kernel_rows_padded")
_C_DEVICE_GATHER_ROWS = telemetry.counter("repro.decode.device_gather.rows")
_C_PALLAS_PACK = telemetry.counter("repro.plan.pallas_pack.events")
_C_TEMPLATE_ROWS = telemetry.counter("repro.plan.template_rows")


class PlanFallback(Exception):
    """A fitted codec cannot lower to a static slot plan (reason in str)."""


def _hashable(v: Any) -> bool:
    try:
        hash(v)
        return True
    except TypeError:
        return False


def _safe_get(get, v, default: int = -1) -> int:
    """Dictionary id lookup that treats unhashable values as misses, so the
    batch path charges the same rows the scalar `conforms` probe would."""
    try:
        return get(v, default)
    except TypeError:
        return default


def _obj_array(values: Sequence, pad: Any = None) -> np.ndarray:
    out = np.empty(len(values) + 1, dtype=object)
    # blitzlint: waive[BL001] -- boundary conversion of heterogeneous Python values into an object array
    for i, v in enumerate(values):
        out[i] = v
    out[len(values)] = pad  # escape symbol row (never produced by the plan)
    return out


# ---------------------------------------------------------------------------
# Per-column lowerings
# ---------------------------------------------------------------------------

class _CatPlan:
    """CategoricalModel -> 1 DiscreteCoder slot; escape rows non-conforming."""

    def __init__(self, model: CategoricalModel) -> None:
        self.m = model
        self.n_slots = 1
        self._values = _obj_array(model.id2value)

    def coders(self) -> List:
        return [self.m.coder]

    def encode(
        self, vals: Sequence, ctx: Dict[str, Sequence]
    ) -> Tuple[np.ndarray, np.ndarray]:
        get = self.m.value2id.get
        ids = np.fromiter((_safe_get(get, v) for v in vals), np.int64, len(vals))
        return ids[:, None], ids >= 0

    def decode(self, syms: np.ndarray, ctx: Dict[str, Any]) -> np.ndarray:
        if sanitize.ENABLED:
            # Alphabet = id2value rows + the escape-pad row appended by
            # _obj_array; the np.minimum clamp below would silently hide
            # a wider (corrupt) code, so check loudly first.
            sanitize.check_code_range(
                syms[:, 0], len(self._values), where="_CatPlan.decode", slot=0
            )
        return self._values[np.minimum(syms[:, 0], len(self._values) - 1)]

    def conforms(self, v, row) -> bool:
        return v in self.m.value2id


class _NumPlan:
    """NumericModel -> level-1 DiscreteCoder + level-2 UniformCoder digits."""

    def __init__(self, model: NumericModel) -> None:
        self.m = model
        self.n_slots = 1 + len(model.l2)

    def coders(self) -> List:
        return [self.m.l1] + list(self.m.l2)

    def encode(
        self, vals: Sequence, ctx: Dict[str, Sequence]
    ) -> Tuple[np.ndarray, np.ndarray]:
        m = self.m
        n = len(vals)
        syms = np.zeros((n, self.n_slots), np.int64)
        ok = np.ones(n, bool)
        try:
            v = np.asarray(vals, dtype=np.float64)
            if v.shape != (n,):
                raise ValueError("ragged numeric column")
        except (TypeError, ValueError):
            # Mixed-type column: convert per element so only the rows that
            # actually fail are charged (scalar `conforms` semantics).
            v = np.zeros(n, np.float64)
            # blitzlint: waive[BL001] -- mixed-type fallback escapes non-conforming values one at a time
            for r, x in enumerate(vals):
                try:
                    v[r] = float(x)
                except (TypeError, ValueError):
                    ok[r] = False
        ok &= np.isfinite(v)
        q = m._quantize(np.where(ok, v, 0.0))
        ok &= (q >= 0) & (q < m.total_steps)
        q = np.clip(q, 0, m.total_steps - 1)
        syms[:, 0] = q // m.G
        j = q % m.G
        for t, w in enumerate(m.radix):
            d = j // w
            j -= d * w
            syms[:, 1 + t] = d
        return syms, ok

    def decode(self, syms: np.ndarray, ctx: Dict[str, Any]) -> np.ndarray:
        m = self.m
        q = syms[:, 0] * m.G
        for t, w in enumerate(m.radix):
            q = q + syms[:, 1 + t] * w
        if m.integer:
            return np.rint(m.vmin + q * m.p).astype(np.int64)
        return m.vmin + (q + 0.5) * m.p

    def conforms(self, v, row) -> bool:
        m = self.m
        try:
            fv = float(v)
        except (TypeError, ValueError):
            return False
        if not math.isfinite(fv):
            return False
        q = math.floor((fv - m.vmin) / m.p + 1e-9)
        return 0 <= q < m.total_steps


class _CondPlan:
    """ConditionalCategoricalModel -> 1 CondSlot keyed on the parent chain.

    The coder of the slot is selected per tuple.  At encode time selection is
    by the parent's *raw value* (as the scalar model does); inside the batch
    decoder it is by the parent chain's decoded *symbols*, which resolve to
    the same sub-model because each (chain-symbol tuple) names exactly one
    parent value.
    """

    n_slots = 1

    def __init__(
        self,
        model: ConditionalCategoricalModel,
        chain_slots: Tuple[int, ...],
        bases: Tuple[int, ...],
        sub_by_tuple: Dict[Tuple[int, ...], CategoricalModel],
    ):
        self.m = model
        self.chain_slots = chain_slots
        self.bases = bases
        self.sub_by_tuple = sub_by_tuple
        packed_coders = {}
        for key_t, sm in sub_by_tuple.items():
            packed_coders[_pack_key(key_t, bases)] = sm.coder
        self.slot = CondSlot(chain_slots, bases, packed_coders, model.marginal.coder)

    def coders(self) -> List:
        return [self.slot]

    def encode(
        self, vals: Sequence, ctx: Dict[str, Sequence]
    ) -> Tuple[np.ndarray, np.ndarray]:
        m = self.m
        pvals = ctx[m.parent]
        ids = np.empty(len(vals), np.int64)
        # blitzlint: waive[BL001] -- conditional-slot encode keys each codebook on the row's parent value
        for r, (pv, v) in enumerate(zip(pvals, vals)):
            sub = m.cond.get(pv, m.marginal) if _hashable(pv) else m.marginal
            ids[r] = _safe_get(sub.value2id.get, v)
        return ids[:, None], ids >= 0

    def decode(self, syms: np.ndarray, ctx: Dict[str, Any]) -> np.ndarray:
        m = self.m
        pvals = ctx[m.parent]
        out = np.empty(syms.shape[0], dtype=object)
        # blitzlint: waive[BL001] -- conditional-slot decode selects a per-row codebook from the parent symbol
        for r in range(syms.shape[0]):
            sub = m.cond.get(pvals[r], m.marginal)
            s = int(syms[r, 0])
            out[r] = sub.id2value[s] if s < len(sub.id2value) else None
        return out

    def conforms(self, v, row) -> bool:
        pv = row[self.m.parent]
        sub = (
            self.m.cond.get(pv, self.m.marginal) if _hashable(pv) else self.m.marginal
        )
        return v in sub.value2id


_DIGIT_CHARS = np.array(list("0123456789"), dtype=object)


Template = Tuple[int, Tuple[bool, ...]]  # (word count, digit flag per word)


def _modal_template(m: StringModel) -> Template:
    """The modal word count ``W`` with each position's modal kind."""
    counts = getattr(m, "n_words_counts", None)
    if not counts:
        raise PlanFallback("string model has no template statistics")
    W = int(counts.most_common(1)[0][0])
    per_pos = getattr(m, "pos_kinds", {}).get(W)
    esc_digits = getattr(m.dict_model, "esc_digits", None)
    digits = []
    for t in range(max(W, 0)):
        kind = -1
        if per_pos is not None and t < len(per_pos) and per_pos[t]:
            kind = int(per_pos[t].most_common(1)[0][0])
        digits.append(kind >= 1 and esc_digits is not None)
    return W, tuple(digits)


def _column_templates(m: StringModel) -> List[Tuple["_StrPlan", float]]:
    """A string column's lowerings with each one's share of the fit's rows.

    The modal template comes first (the column's plan before families);
    then the fit's joint template histogram in descending frequency, until
    the templates cover ``TEMPLATE_COVERAGE`` of the rows, reach
    ``MAX_COLUMN_TEMPLATES``, or fall below ``MIN_TEMPLATE_SHARE``.
    """
    first = _modal_template(m)
    hist = getattr(m, "templates", None) or {}
    total = sum(hist.values())
    share = hist.get(first, 0) / total if total else 1.0
    out = [(_StrPlan(m, first), share)]
    covered = share
    for tpl, count in sorted(hist.items(), key=lambda kv: -kv[1]):
        if len(out) >= MAX_COLUMN_TEMPLATES or covered >= TEMPLATE_COVERAGE:
            break
        share = count / total
        if share < MIN_TEMPLATE_SHARE:
            break
        if tpl == first:
            continue
        try:
            out.append((_StrPlan(m, tpl), share))
        except PlanFallback:
            continue  # a shape the scalar coder has no fixed layout for
        covered += share
    return out


class _StrPlan:
    """StringModel -> fixed word/delimiter template slots.

    Requires ``block_tuples == 1`` (enforced at plan level): the per-block
    prefix queue is then always empty at encode time, so the match slot is
    the constant "no prefix" symbol and no prefix-length slots are emitted.
    The template fixes the word count ``W`` and each word position's kind:
    a dictionary word (one dict-coder slot) or an all-digit token of up to
    ``cap`` digits (constant ``esc_digits`` + length slots, then ``cap``
    uniform digit slots — the scalar encoder's cap-padded digit path,
    flattened, so street numbers and sku/phone runs of varying width share
    one layout).  The scalar coder's symbols for a row depend only on the
    row's ``(W, kinds)``, so a plan per template emits them bit for bit.
    Rows with a different segment count, a kind mismatch or over-cap digit
    run at any position, dictionary-miss words, or escape delimiters are
    non-conforming.
    """

    def __init__(self, model: StringModel, template: Optional[Template] = None) -> None:
        m = model
        self.m = m
        self.W, digits = template if template is not None else _modal_template(m)
        if self.W < 1:
            raise PlanFallback("string template has no words")
        n_m = m.n_model
        q = int(n_m._quantize(self.W))
        if not (0 <= q < n_m.total_steps):
            raise PlanFallback("string template word count not encodable")
        n_syms = [q // n_m.G]
        j = q % n_m.G
        for w in n_m.radix:
            d = j // w
            j -= d * w
            n_syms.append(d)
        self._n_syms = np.asarray(n_syms, np.int64)
        self._nn = len(n_syms)
        # Per word-position mode: None = dictionary word (1 slot), cap >= 1
        # = all-digit token of up to ``cap`` digits (2 constant slots + cap
        # digit slots; the scalar coder pads every digit token to the same
        # cap, so conforming streams stay bit-identical).  ``_digit_modal``
        # keeps the most common length for the fixed-shape pre-pass.
        per_pos = getattr(m, "pos_kinds", {}).get(self.W)
        self._esc_digits = getattr(m.dict_model, "esc_digits", None)
        self._modes: List[Optional[int]] = []
        self._digit_modal: List[Optional[int]] = []
        for t in range(self.W):
            mode: Optional[int] = None
            modal: Optional[int] = None
            if digits[t]:
                cap = int(m.digit_cap(self.W, t))
                if self._esc_digits is None or cap < 1:
                    raise PlanFallback("string template digit run has no cap")
                mode = cap
                kinds = per_pos[t] if per_pos is not None and t < len(per_pos) else {}
                lens = Counter({k: c for k, c in kinds.items() if k >= 1})
                modal = int(lens.most_common(1)[0][0]) if lens else None
            self._modes.append(mode)
            self._digit_modal.append(modal)
        # Slot offsets (relative to the first template slot) of each word
        # position and of the delimiter that follows it.
        self._word_off: List[int] = []
        self._delim_off: List[int] = []
        off = 0
        for t, mode in enumerate(self._modes):
            self._word_off.append(off)
            off += 1 if mode is None else 2 + mode
            if t < self.W - 1:
                self._delim_off.append(off)
                off += 1
        self.n_slots = 1 + self._nn + off
        self._words = _obj_array(
            [wb.decode("utf-8", errors="replace") for wb in m.dict_model.id2value],
            pad="",
        )
        self._delims = _obj_array(list(m.delim_model.id2value), pad="")
        self._fixed = self._build_fixed_spec()

    def _build_fixed_spec(self) -> Optional[Dict[str, Any]]:
        """Character-matrix spec for fully fixed-shape templates.

        When every word position is a fixed-length digit run or a
        near-constant dictionary word, conforming strings all share one
        exact character layout, so a whole batch lowers through vectorized
        char-code compares with no per-row Python.  Rows failing the check
        fall back to the exact row-wise encoder, keeping the fast mask
        identical to :meth:`conforms`.
        """
        m = self.m
        per_words = getattr(m, "pos_words", {}).get(self.W)
        base = 1 + self._nn
        spec: List[Tuple[str, int, int, int, Any]] = []
        coff = 0
        for t, mode in enumerate(self._modes):
            if mode is not None:
                # Fixed layout needs one exact char width: use the modal
                # digit length; other lengths re-check through the exact
                # row-wise encoder.
                modal = self._digit_modal[t]
                if modal is None or modal > mode:
                    return None
                spec.append(
                    ("digit", coff, modal, base + self._word_off[t], mode)
                )
                coff += modal
            else:
                if per_words is None or t >= len(per_words) or not per_words[t]:
                    return None
                pw = per_words[t]
                if None in pw:
                    return None
                w, c = pw.most_common(1)[0]
                if c < 0.95 * sum(pw.values()):
                    return None
                wid = m.dict_model.value2id.get(w.encode("utf-8"))
                if wid is None:
                    return None
                codes = np.array([ord(ch) for ch in w], np.uint32)
                spec.append(
                    ("word", coff, len(w), base + self._word_off[t], (codes, wid))
                )
                coff += len(w)
            if t < self.W - 1:
                spec.append(("delim", coff, 1, base + self._delim_off[t], None))
                coff += 1
        lut = np.full(128, -1, np.int64)
        for d, did in m.delim_model.value2id.items():
            if isinstance(d, str) and len(d) == 1 and ord(d) < 128:
                lut[ord(d)] = did
        return {"t_len": coff, "spec": spec, "lut": lut}

    def coders(self) -> List:
        m = self.m
        out = [m.i_model, m.n_model.l1, *m.n_model.l2]
        for t, mode in enumerate(self._modes):
            out.append(m.dict_model.coder)
            if mode is not None:
                out.append(m.digit_len_model)
                out.extend([_DIGIT10] * mode)
            if t < self.W - 1:
                out.append(m.delim_model.coder)
        return out

    def encode(
        self, vals: Sequence, ctx: Dict[str, Sequence]
    ) -> Tuple[np.ndarray, np.ndarray]:
        if self._fixed is not None and len(vals):
            return self._encode_fixed(vals)
        return self._encode_rowwise(vals)

    def _encode_fixed(self, vals: Sequence) -> Tuple[np.ndarray, np.ndarray]:
        fixed = self._fixed
        assert fixed is not None
        t_len = fixed["t_len"]
        sv = [v if isinstance(v, str) else str(v) for v in vals]
        n = len(sv)
        ua = np.array(sv, dtype=f"U{t_len + 1}")
        cm = ua.view(np.uint32).reshape(n, t_len + 1)
        ok = np.char.str_len(ua) == t_len
        syms = np.zeros((n, self.n_slots), np.int64)
        base = 1 + self._nn
        syms[:, 0] = self.m.K
        syms[:, 1:base] = self._n_syms
        lut = fixed["lut"]
        for kind, coff, ln, slot, payload in fixed["spec"]:
            if kind == "digit":
                d = cm[:, coff:coff + ln].astype(np.int64) - 48
                ok &= ((d >= 0) & (d <= 9)).all(axis=1)
                syms[:, slot] = self._esc_digits
                syms[:, slot + 1] = ln - 1
                syms[:, slot + 2:slot + 2 + ln] = d
            elif kind == "word":
                codes, wid = payload
                if ln:
                    ok &= (cm[:, coff:coff + ln] == codes).all(axis=1)
                syms[:, slot] = wid
            else:  # delim
                ch = cm[:, coff].astype(np.int64)
                did = lut[np.clip(ch, 0, 127)]
                ok &= (ch < 128) & (did >= 0)
                syms[:, slot] = np.maximum(did, 0)
        bad = np.nonzero(~ok)[0]
        if bad.size:
            # Non-matching rows may still conform through other dictionary
            # words — re-check them with the exact row-wise encoder.
            sub_syms, sub_ok = self._encode_rowwise([sv[i] for i in bad])
            syms[bad] = sub_syms
            ok[bad] = sub_ok
        return syms, ok

    def _encode_rowwise(self, vals: Sequence) -> Tuple[np.ndarray, np.ndarray]:
        m, W = self.m, self.W
        n = len(vals)
        syms = np.zeros((n, self.n_slots), np.int64)
        ok = np.ones(n, bool)
        wget = m.dict_model.value2id.get
        dget = m.delim_model.value2id.get
        base = 1 + self._nn
        modes, woff, doff = self._modes, self._word_off, self._delim_off
        # blitzlint: waive[BL001] -- string tokenizer walks variable-length values on the fit/escape path
        for r, v in enumerate(vals):
            s = v if isinstance(v, str) else str(v)
            segs = m._split(s)
            if (len(segs) + 1) // 2 != W:
                ok[r] = False
                continue
            syms[r, 0] = m.K                      # empty queue: no prefix hit
            syms[r, 1:base] = self._n_syms
            for t, tok in enumerate(segs):
                if t % 2 == 1:
                    did = dget(tok)
                    if did is None:
                        ok[r] = False
                        break
                    syms[r, base + doff[t // 2]] = did
                    continue
                mode = modes[t // 2]
                off = base + woff[t // 2]
                if mode is None:
                    wid = wget(tok.encode("utf-8"))
                    if wid is None:               # dict miss (or digit token)
                        ok[r] = False
                        break
                    syms[r, off] = wid
                else:
                    if len(tok) > mode or not _is_digit_token(tok):
                        ok[r] = False
                        break
                    syms[r, off] = self._esc_digits
                    syms[r, off + 1] = len(tok) - 1
                    for i, ch in enumerate(tok):
                        syms[r, off + 2 + i] = ord(ch) - 48
                    # slots past len(tok) stay 0 — the scalar cap padding
        return syms, ok

    def decode(self, syms: np.ndarray, ctx: Dict[str, Any]) -> np.ndarray:
        base = 1 + self._nn
        cols = []
        for t, mode in enumerate(self._modes):
            off = base + self._word_off[t]
            if mode is None:
                tab = self._words
                cols.append(tab[np.minimum(syms[:, off], len(tab) - 1)])
            else:
                # variable-length digit run: grow each row's string up to
                # its decoded length (<= mode concat passes, vectorized)
                lens = np.minimum(syms[:, off + 1], mode - 1) + 1
                col = _DIGIT_CHARS[np.minimum(syms[:, off + 2], 9)].copy()
                for i in range(1, mode):
                    live = lens > i
                    if not live.any():
                        break
                    col[live] = col[live] + _DIGIT_CHARS[
                        np.minimum(syms[live, off + 2 + i], 9)
                    ]
                cols.append(col)
            if t < self.W - 1:
                tab = self._delims
                doff = base + self._delim_off[t]
                cols.append(tab[np.minimum(syms[:, doff], len(tab) - 1)])
        if len(cols) == 1:
            return cols[0]
        return np.asarray(["".join(parts) for parts in zip(*cols)], dtype=object)

    def conforms(self, v, row) -> bool:
        s = v if isinstance(v, str) else str(v)
        segs = self.m._split(s)
        if (len(segs) + 1) // 2 != self.W:
            return False
        wids = self.m.dict_model.value2id
        dids = self.m.delim_model.value2id
        for t, tok in enumerate(segs):
            if t % 2 == 1:
                if tok not in dids:
                    return False
                continue
            mode = self._modes[t // 2]
            if mode is None:
                if tok.encode("utf-8") not in wids:
                    return False
            elif len(tok) > mode or not _is_digit_token(tok):
                return False
        return True


# ---------------------------------------------------------------------------
# Table plan
# ---------------------------------------------------------------------------

def _pack_key(key_t: Tuple[int, ...], bases: Tuple[int, ...]) -> int:
    out = 0
    for k, b in zip(key_t, bases):
        out = out * b + k
    return out


def _parent_enum(
    plan_of: Dict[str, Tuple[Any, int]], parent: str
) -> Tuple[Tuple[int, ...], List[Tuple[Tuple[int, ...], Any]]]:
    """Enumerate (chain-symbol tuple, parent value) pairs for a parent column."""
    cp, off = plan_of[parent]
    if isinstance(cp, _CatPlan):
        return (off,), [((i,), v) for i, v in enumerate(cp.m.id2value)]
    if isinstance(cp, _CondPlan):
        chain = cp.chain_slots + (off,)
        out = []
        for key_t, sub in cp.sub_by_tuple.items():
            for i, v in enumerate(sub.id2value):
                out.append((key_t + (i,), v))
        return chain, out
    raise PlanFallback(f"conditional parent {parent!r} is not a categorical column")


def _build_cond(
    model: ConditionalCategoricalModel, plan_of: Dict[str, Tuple[Any, int]], name: str
) -> _CondPlan:
    if model.parent not in plan_of:
        raise PlanFallback(
            f"column {name!r}: parent {model.parent!r} not ordered before it"
        )
    chain, enum = _parent_enum(plan_of, model.parent)
    if len(enum) > MAX_COND_KEYS:
        raise PlanFallback(
            f"column {name!r}: {len(enum)} parent combinations exceed cap"
        )
    bases = tuple(max(k[i] for k, _ in enum) + 2 for i in range(len(chain)))
    sub_by_tuple = {key_t: model.cond.get(pv, model.marginal) for key_t, pv in enum}
    return _CondPlan(model, chain, bases, sub_by_tuple)


class TablePlan:
    """A compiled codec: static slots + vectorized value<->symbol tables.

    One member of the table's plan family (:func:`compile_plan`):
    ``family`` lists every member, modal plan first, and ``template`` is
    this member's index there — the id a block encoded under it stores.
    The first member holds the escape counters for the whole family.
    """

    def __init__(
        self, codec: Any, lowerings: List[Tuple[str, Any, int]]
    ) -> None:
        self.codec = codec
        self.order = list(codec.order)
        self.lowerings = lowerings
        self.by_column = {name: (cp, off) for name, cp, off in lowerings}
        self.lam = codec.lam
        self.family: List[TablePlan] = [self]
        self.template = 0
        self._family_bits: Optional[Tuple[int, ...]] = None
        # Per-column escape counters (§5-style dynamic value sets): how many
        # values failed to lower at encode time — the signal the adaptive
        # maintenance layer (DESIGN.md §4) watches to decide a column's model
        # has drifted.  Both the batch `encode_rows` masks and the scalar
        # `row_conforms` probe charge *every* non-conforming column of a row
        # (identical semantics, tested in tests/test_plan_escapes.py).
        # `escape_counts`/`rows_seen` are cumulative for the plan's lifetime;
        # the `window_*` pair resets on `reset_escapes()` so drift detection
        # sees rates over the current window, not the whole history.
        self.escape_counts: Dict[str, int] = {n: 0 for n, _, _ in lowerings}
        self.window_escapes: Dict[str, int] = {n: 0 for n, _, _ in lowerings}
        self.rows_seen = 0
        self.window_rows = 0
        self._accounting_paused = False
        self.coders: List = []
        for _, cp, _ in lowerings:
            self.coders.extend(cp.coders())
        self.S = len(self.coders)
        self.pallas_ok = (self.lam == TOTAL and all(
            isinstance(c, (DiscreteCoder, UniformCoder)) for c in self.coders))
        self._tables = None
        self._m_bits: Optional[Tuple[int, ...]] = None
        # Pre-build the 2**16 decoding maps (Fig 11): turns the per-slot
        # alias lookup into two gathers on the hot decode path.  Conditional
        # sub-coders are skipped — there can be thousands of them, and each
        # map costs ~0.75 MiB; they decode via the alias tables instead.
        for c in self.coders:
            if isinstance(c, DiscreteCoder):
                c.build_lut()

    # -- escape accounting (refit hook, DESIGN.md §4) --------------------
    def _charge(self, name: str, misses: int = 1) -> None:
        if self._accounting_paused:
            return
        self.escape_counts[name] += misses
        self.window_escapes[name] += misses

    def _note_rows(self, n: int) -> None:
        if self._accounting_paused:
            return
        self.rows_seen += n
        self.window_rows += n

    @contextlib.contextmanager
    def pause_escape_accounting(self) -> Iterator[None]:
        """Suspend counter updates for maintenance re-encodes.

        Migration re-encodes rows that already escaped once; charging them
        again would make maintenance traffic indistinguishable from
        workload drift and feed the monitor a signal it generated itself.
        """
        self._accounting_paused = True
        try:
            yield
        finally:
            self._accounting_paused = False

    def reset_escapes(self) -> Dict[str, int]:
        """Close the current escape window; returns its per-column counts.

        Cumulative ``escape_counts``/``rows_seen`` are untouched — drift
        detection consumes windows, long-horizon stats the totals.
        """
        snapshot = dict(self.window_escapes)
        for k in self.window_escapes:
            self.window_escapes[k] = 0
        self.window_rows = 0
        return snapshot

    def escape_rates(self) -> Dict[str, float]:
        """Per-column escape rate over the current window (0.0 if empty)."""
        n = self.window_rows
        if not n:
            return {k: 0.0 for k in self.window_escapes}
        return {k: v / n for k, v in self.window_escapes.items()}

    # -- encode ----------------------------------------------------------
    def _encode(
        self, rows: Sequence[Dict[str, Any]]
    ) -> Tuple[np.ndarray, np.ndarray, Dict[str, np.ndarray]]:
        """Rows -> (syms int64[N, S], conforming bool[N], per-column masks
        of the rows each column refuses); charges nothing."""
        n = len(rows)
        cols = {name: [r[name] for r in rows] for name in self.order}
        syms = np.zeros((n, self.S), np.int64)
        ok = np.ones(n, bool)
        refused: Dict[str, np.ndarray] = {}
        for name, cp, off in self.lowerings:
            try:
                s_col, o = cp.encode(cols[name], cols)
            except Exception:
                refused[name] = np.ones(n, bool)
                ok[:] = False
                continue
            syms[:, off:off + cp.n_slots] = s_col
            if not o.all():
                refused[name] = ~o
            ok &= o
        return syms, ok, refused

    def _route(
        self, rows: Sequence[Dict[str, Any]]
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Dict[int, np.ndarray]]:
        """Encode under this member, then offer the rows it refuses to the
        family's other members in order, each seeing only the rows the
        earlier ones refused.

        Returns this member's ``(syms, ok)``, each row's template (-1 when
        no member takes it) and ``{template: syms of its rows in row
        order}``.  Only rows no member takes are charged, per column as
        this member refused them — the escape counters keep their meaning.
        """
        head = self.family[0]
        head._note_rows(len(rows))
        syms, ok, refused = self._encode(rows)
        tmpl = np.where(ok, self.template, -1)
        parts: Dict[int, np.ndarray] = {}
        if ok.any():
            parts[self.template] = syms if ok.all() else syms[ok]
        rest = np.nonzero(~ok)[0]
        for member in self.family:
            if not rest.size:
                break
            if member is self:
                continue
            s_m, o_m, _ = member._encode([rows[i] for i in rest.tolist()])
            if o_m.any():
                tmpl[rest[o_m]] = member.template
                parts[member.template] = s_m[o_m]
            rest = rest[~o_m]
        if rest.size:
            for name, mask in refused.items():
                misses = int(np.count_nonzero(mask[rest]))
                if misses:
                    head._charge(name, misses)
        moved = int(np.count_nonzero(tmpl > 0))
        if moved:
            _C_TEMPLATE_ROWS.add(moved)
        return syms, ok, tmpl, parts

    def encode_rows(self, rows: Sequence[Dict[str, Any]]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Rows -> (syms int64[N, S], conforming bool[N]) under this plan.

        A row this plan refuses is charged in :attr:`escape_counts` only
        if no other member of the family takes it (see :meth:`encode_family`).
        """
        with telemetry.span("repro.core.encode.scalar"):
            syms, ok, _, _ = self._route(rows)
        return syms, ok

    def encode_family(
        self, rows: Sequence[Dict[str, Any]]
    ) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
        """Rows -> (template int64[N], {template: syms of its rows}).

        The modal plan tries every row; only the rows it refuses are tried
        against the next members.  Template -1 marks a row no member takes:
        it escapes to the scalar coder, charged exactly as before.
        """
        with telemetry.span("repro.core.encode.scalar"):
            _, _, tmpl, parts = self.family[0]._route(rows)
        return tmpl, parts

    def encode_batch(self, syms: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Symbols -> CSR ``(codes uint16, offsets int64[N+1])``."""
        with telemetry.span("repro.core.encode"):
            codes, offsets = vectorized.encode_batch(syms, self.coders, self.lam)
            codes = checked_astype(codes, np.uint16, where="encode_batch codes")
            _C_ENCODE_ROWS.add(syms.shape[0])
        return codes, offsets

    def _refused(self, row: Dict[str, Any]) -> List[str]:
        """Columns of ``row`` this plan cannot lower (pure-Python checks)."""
        bad = []
        for name, cp, _ in self.lowerings:
            try:
                good = cp.conforms(row[name], row)
            except (TypeError, KeyError, ValueError):
                good = False
            if not good:
                bad.append(name)
        return bad

    def row_template(self, row: Dict[str, Any]) -> int:
        """Cheap scalar check: the template of the family member this row
        takes, or -1 when it escapes.

        Pure-Python per-column checks (no numpy) so the per-insert cost is a
        few dict lookups, not a 1-row batch encode.  A row no member takes
        is charged for every column this plan refuses — the same per-column
        semantics as the batch :meth:`encode_rows` masks, so drift rates
        don't depend on which encode path a row took.
        """
        head = self.family[0]
        head._note_rows(1)
        bad = self._refused(row)
        if not bad:
            return self.template
        for member in self.family:
            if member is not self and not member._refused(row):
                if member.template:
                    _C_TEMPLATE_ROWS.inc()
                return member.template
        for name in bad:
            head._charge(name)
        return -1

    def row_conforms(self, row: Dict[str, Any]) -> bool:
        """Would this row take this plan's fast path?  Charged as
        :meth:`row_template` charges."""
        return self.row_template(row) == self.template

    # -- decode ----------------------------------------------------------
    def decode_batch(self, codes: np.ndarray, offsets: np.ndarray,
                     n_tuples: Optional[int] = None) -> np.ndarray:
        return vectorized.decode_batch(
            codes, offsets, self.coders, n_tuples=n_tuples, lam=self.lam
        )

    def decode_select(
        self,
        codes: np.ndarray,
        offsets: np.ndarray,
        rows: np.ndarray,
        backend: str = "numpy",
    ) -> np.ndarray:
        """Random-access decode of selected tuples -> syms int64[R, S].

        ``codes`` is a host array, or :class:`~repro.core.mirror.MirroredCodes`:
        the same codes beside their copy in HBM (a table's arena mirror),
        which the kernel path gathers from without an upload.
        """
        with telemetry.span("repro.core.decode"):
            if backend == "pallas":
                out = self._decode_select_pallas(codes, offsets, rows)
            else:
                host = codes.host if isinstance(codes, MirroredCodes) else codes
                out = vectorized.decode_select(host, offsets, self.coders, rows,
                                               self.lam)
            _C_DECODE_ROWS.add(int(np.size(rows)))
        return out

    def _decode_select_pallas(
        self, codes: Any, offsets: np.ndarray, rows: np.ndarray
    ) -> np.ndarray:
        if not self.pallas_ok:
            raise PlanFallback("plan has conditional slots; Pallas ineligible")
        import jax.numpy as jnp
        from repro.kernels.delayed_decode import BLOCK_T, delayed_decode_runs
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return np.zeros((0, self.S), np.int64)
        # Prepare: everything before the result is awaited (each row's
        # start and length, the codes' sync or upload, kernel dispatch).
        with telemetry.span("repro.decode.prepare"):
            # Pad the batch to a pow2 bucket so jax compiles one kernel per
            # bucket instead of one per distinct batch size.  The floor is
            # the kernel's row tile: every batch up to BLOCK_T rows shares
            # one compiled program (the prepared-op cache keys on finer
            # buckets, DESIGN.md §11).
            n = rows.size
            padded = max(BLOCK_T, 1 << (n - 1).bit_length())
            if padded != n:
                rows = np.concatenate([rows, np.full(padded - n, rows[-1], np.int64)])
            # The kernel's slots are the family's (kernel_m_bits): at least
            # this member's; the slots past its own decode zeros, unread.
            tables, m_bits = self.pallas_tables()
            runs = np.empty((2, padded), np.int64)
            runs[0] = offsets[rows]
            runs[1] = offsets[rows + 1] - runs[0]
            runs = checked_astype(runs, np.int32, where="decode_select runs")
            # The chip gathers each row's run from codes in HBM: the
            # caller's mirror, else its host codes uploaded in one copy.
            if isinstance(codes, MirroredCodes):
                dev = codes.device()
                _C_DEVICE_GATHER_ROWS.add(n)
            else:
                dev = upload_codes(codes)
            out = delayed_decode_runs(dev, jnp.asarray(runs), tables, m_bits)
        # Wait: the device's time left after dispatch plus the copy back.
        with telemetry.span("repro.decode.wait"):
            syms = np.asarray(out).astype(np.int64)[:n, :self.S]
        _C_KERNEL_ROWS.add(n)
        _C_KERNEL_ROWS_PADDED.add(padded)
        return syms

    def pallas_tables(self) -> Tuple[Any, Tuple[int, ...]]:
        """Lazy ``(tables f32[R, 7 * 128], m_bits)`` in the kernel's layout,
        at the family's slot bits (:meth:`kernel_m_bits`)."""
        if self._tables is None:
            with telemetry.span("repro.plan.pallas_pack"):
                from repro.kernels.ops import pack_slot_tables
                self._tables, self._m_bits = pack_slot_tables(
                    self.coders, self.family[0].kernel_m_bits()
                )
                _C_PALLAS_PACK.inc()
        return self._tables, self._m_bits

    def kernel_m_bits(self) -> Optional[Tuple[int, ...]]:
        """Per-slot bucket bits every family member packs its kernel tables
        at: the widest member's at each slot, over the longest member's
        slots, so the whole family shares one compiled kernel per row
        bucket.  ``None`` for a family of one (its own bits)."""
        if len(self.family) == 1:
            return None
        if self._family_bits is None:
            from repro.kernels.ops import coder_m_bits
            per = [[coder_m_bits(c) for c in m.coders] for m in self.family]
            width = max(len(p) for p in per)
            self._family_bits = tuple(
                max(p[s] for p in per if s < len(p)) for s in range(width)
            )
        return self._family_bits

    def decode_syms_to_rows(
        self, syms: np.ndarray, columns: Optional[Sequence[str]] = None
    ) -> List[Dict[str, Any]]:
        """Symbols -> row dicts (vectorized per-column reconstruction).

        ``columns`` restricts materialization to a projection: only the
        requested columns (plus any conditional-parent ancestors their
        decode needs for context) are reconstructed, and the returned
        dicts hold exactly the requested columns.
        """
        with telemetry.span("repro.decode.materialize"):
            return self._syms_to_rows(syms, columns)

    def _syms_to_rows(
        self, syms: np.ndarray, columns: Optional[Sequence[str]]
    ) -> List[Dict[str, Any]]:
        ctx: Dict[str, Any] = {}
        need: Optional[set] = None
        if columns is not None:
            unknown = set(columns) - set(self.order)
            if unknown:
                raise KeyError(f"unknown columns: {sorted(unknown)}")
            need = set(columns)
            # Parents precede children in lowering order, so a reversed
            # walk closes the ancestor chain in one pass.
            for name, cp, _ in reversed(self.lowerings):
                if name in need and isinstance(cp, _CondPlan):
                    need.add(cp.m.parent)
        for name, cp, off in self.lowerings:
            if need is not None and name not in need:
                continue
            ctx[name] = cp.decode(syms[:, off:off + cp.n_slots], ctx)
        names = (self.order if columns is None
                 else [n for n in self.order if n in set(columns)])
        # Bulk-convert numpy columns to Python objects (ints/floats/strs):
        # much faster than boxing one numpy scalar per field, and the row
        # dicts then hold the same native types the scalar decoder emits.
        cols = [c.tolist() if isinstance(c, np.ndarray) else list(c)
                for c in (ctx[nm] for nm in names)]
        return [dict(zip(names, vals)) for vals in zip(*cols)]


# ---------------------------------------------------------------------------
# Code-space predicate lowering (scan engine, DESIGN.md §8)
#
# The scan engine (repro.scan) translates value-space predicates into this
# plan version's symbol space once per scan, then evaluates them against raw
# code streams / decoded symbol prefixes without materializing rows.  The
# helpers live here because they reach into the per-column lowering internals
# (_CatPlan vocabularies, _NumPlan quantization grids).
# ---------------------------------------------------------------------------

def scan_lowering(plan: TablePlan, name: str) -> Optional[Tuple[str, Any, int]]:
    """``('cat'|'num', colplan, slot_offset)`` when predicates on column
    ``name`` are code-space evaluable under ``plan``, else None (string and
    conditional columns fall back to decode-then-filter)."""
    ent = plan.by_column.get(name)
    if ent is None:
        return None
    cp, off = ent
    if isinstance(cp, _CatPlan):
        return ("cat", cp, off)
    if isinstance(cp, _NumPlan):
        return ("num", cp, off)
    return None


def lower_cat_ids(cp: _CatPlan, values: Sequence[Any]) -> np.ndarray:
    """Translate literal values to this version's category ids (sorted).

    Literals outside the vocabulary are dropped: a *fast* row always encodes
    an in-vocabulary id, so a missing literal can never match a fast block.
    """
    ids = set()
    # blitzlint: waive[BL001] -- fit-time categorical lowering, not the per-op hot path
    for v in values:
        i = _safe_get(cp.m.value2id.get, v)
        if i >= 0:
            ids.add(int(i))
    return np.asarray(sorted(ids), dtype=np.int64)


def lower_cat_range_ids(cp: _CatPlan, lo: Any, hi: Any) -> Optional[np.ndarray]:
    """Ids of vocabulary values inside ``[lo, hi]`` — range predicates on
    int columns that specialized to a categorical vocabulary.  ``None`` when
    the vocabulary does not compare against the bounds (mixed types)."""
    ids = []
    try:
        for i, v in enumerate(cp.m.id2value):
            if (lo is None or v >= lo) and (hi is None or v <= hi):
                ids.append(i)
    except TypeError:
        return None
    return np.asarray(ids, dtype=np.int64)


def _num_decoded_at(m: NumericModel, q: int) -> float:
    """The value the decoder reconstructs for quantized step ``q``."""
    if m.integer:
        return float(int(round(m.vmin + q * m.p)))
    return m.vmin + (q + 0.5) * m.p


def lower_num_interval(
    m: NumericModel, lo: Optional[float], hi: Optional[float]
) -> Optional[Tuple[int, int]]:
    """``(qlo, qhi)`` with decoded(q) ∈ [lo, hi]  ⇔  qlo <= q <= qhi.

    Decode is monotone non-decreasing in q, so a value-space interval maps
    to one q-interval: seed each endpoint with the quantization guess, then
    correct against the actual decoded values (never off by more than a
    step or two).  ``None`` bounds are open; returns ``None`` when no
    conforming value can match.
    """
    steps = m.total_steps
    if lo is None:
        qlo = 0
    else:
        flo = float(lo)
        g = min(max(int(math.floor((flo - m.vmin) / m.p + 1e-9)), 0), steps - 1)
        while g > 0 and _num_decoded_at(m, g - 1) >= flo:
            g -= 1
        while g < steps and _num_decoded_at(m, g) < flo:
            g += 1
        qlo = g
    if hi is None:
        qhi = steps - 1
    else:
        fhi = float(hi)
        g = min(max(int(math.floor((fhi - m.vmin) / m.p + 1e-9)), 0), steps - 1)
        while g < steps - 1 and _num_decoded_at(m, g + 1) <= fhi:
            g += 1
        while g >= 0 and _num_decoded_at(m, g) > fhi:
            g -= 1
        qhi = g
    if qlo >= steps or qhi < 0 or qlo > qhi:
        return None
    return (int(qlo), int(qhi))


def num_q_of_syms(cp: _NumPlan, syms: np.ndarray) -> np.ndarray:
    """Quantized step q per row from a numeric column's symbol slots."""
    m = cp.m
    q = syms[:, 0] * m.G
    for t, w in enumerate(m.radix):
        q = q + syms[:, 1 + t] * w
    return q


def slot0_match_lut(coder, match_ids: np.ndarray) -> Optional[np.ndarray]:
    """``bool[TOTAL]``: does a raw slot-0 stream code decode to a match id?

    Valid because slot 0 is always physical (delayed coding starts with an
    option-count product of 1, below any lambda) and ``_lut_sym[code]`` is
    that code's exact slot-0 symbol regardless of the delayed payload its
    remaining bits carry — so gathering the LUT at each block's first code
    evaluates the predicate without decoding anything.
    """
    if not isinstance(coder, DiscreteCoder):
        return None
    if coder._lut_sym is None:
        coder.build_lut()
    return np.isin(coder._lut_sym, np.asarray(match_ids, dtype=np.int64))


def quantize_slack(model: Any) -> Optional[float]:
    """Worst-case ``|decoded - raw|`` for conforming values under ``model``.

    Zone maps hold *raw* value bounds while predicates match *decoded*
    values, so pruning must widen the zone test by this slack or a value
    quantized across a bound would be falsely pruned.  ``None`` = unbounded
    (never zone-prune on a column using this model); escapes decode to the
    exact raw value and need no slack.
    """
    if isinstance(model, (CategoricalModel, ConditionalCategoricalModel)):
        return 0.0
    if isinstance(model, NumericModel):
        return float(model.p)
    return None


def decode_select_prefix(
    plan: TablePlan, codes: np.ndarray, offsets: np.ndarray, rows: np.ndarray, upto: int
) -> np.ndarray:
    """Truncated random-access decode of the first ``upto`` slots.

    Delayed coding reads the stream strictly forward, so a slot prefix
    consumes a prefix of each row's code run: ``decode_batch`` over the
    truncated coder list with an explicit ``n_tuples`` (which skips the
    full-stream alignment assert) decodes it exactly.  Predicate
    evaluation uses this to touch only the slots the predicates name.
    """
    return vectorized.decode_select(
        codes, offsets, plan.coders[:upto], np.asarray(rows, np.int64), plan.lam
    )


def _lower(
    codec, choice: Dict[str, Any], shared: Dict[str, Any]
) -> List[Tuple[str, Any, int]]:
    """One plan's lowerings; string columns take their lowering from
    ``choice``, every other column lowers as its model says.  Lowerings
    that do not depend on slot offsets are kept in ``shared`` for the
    family's other members."""
    lowerings: List[Tuple[str, Any, int]] = []
    plan_of: Dict[str, Tuple[Any, int]] = {}
    offset = 0
    for name in codec.order:
        m = codec.models[name]
        if name in choice:
            cp: Any = choice[name]
        elif name in shared:
            cp = shared[name]
        elif isinstance(m, ConditionalCategoricalModel):
            cp = _build_cond(m, plan_of, name)  # keyed on parent slot offsets
        elif isinstance(m, CategoricalModel):
            cp = shared[name] = _CatPlan(m)
        elif isinstance(m, NumericModel):
            cp = shared[name] = _NumPlan(m)
        elif isinstance(m, TimeSeriesModel):
            raise PlanFallback(
                f"column {name!r}: time-series model is stateful across rows"
            )
        else:
            raise PlanFallback(
                f"column {name!r}: {type(m).__name__} has no slot lowering"
            )
        lowerings.append((name, cp, offset))
        plan_of[name] = (cp, offset)
        offset += cp.n_slots
    return lowerings


def _family_choices(
    options: Dict[str, List[Tuple[Any, float]]]
) -> List[Dict[str, Any]]:
    """The family's string lowerings: every column's first template, then
    the cross product's other combinations in descending joint share (a
    beam of ``MAX_FAMILY`` suffices: a prefix outside the best ``K``
    prefixes cannot start one of the best ``K`` products), ``MAX_FAMILY``
    in all."""
    names = list(options)
    beam: List[Tuple[float, Tuple[int, ...]]] = [(1.0, ())]
    for name in names:
        beam = sorted(
            ((sc * options[name][i][1], key + (i,))
             for (sc, key), i in itertools.product(beam, range(len(options[name])))),
            key=lambda e: (-e[0], e[1]),
        )[:MAX_FAMILY]
    first = (0,) * len(names)
    keys = [first] + [key for _, key in beam if key != first][:MAX_FAMILY - 1]
    return [{n: options[n][i][0] for n, i in zip(names, key)} for key in keys]


def compile_plan(codec) -> TablePlan:
    """Lower a fitted TableCodec to its plan family, or raise PlanFallback.

    Returns the family's first member, the modal plan; a table whose string
    columns each have one template compiles to a family of one.
    """
    if codec.block_tuples != 1:
        raise PlanFallback(
            f"block_tuples={codec.block_tuples}: multi-tuple blocks chain "
            "virtual bits across rows")
    options = {
        name: _column_templates(codec.models[name])
        for name in codec.order
        if isinstance(codec.models[name], StringModel)
    }
    shared: Dict[str, Any] = {}
    family = [TablePlan(codec, _lower(codec, choice, shared))
              for choice in _family_choices(options)]
    for t, plan in enumerate(family):
        plan.family = family
        plan.template = t
    return family[0]
