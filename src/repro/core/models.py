"""Semantic column models (§4): value <-> slot-symbol translation.

Each model maps a column value to a short sequence of (coder, symbol) slots
and back.  Models *estimate distributions* rather than pinning static
dictionaries, so unseen values stay encodable through explicit escape paths
(the paper's "dynamic value set" requirement for OLTP inserts).

Models compose (§4.3): the string model nests categorical, numeric and
Markov sub-models; the numeric model nests a categorical level-1 and uniform
level-2 coders.
"""

from __future__ import annotations

import math
from collections import Counter, deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .coders import TOTAL, DiscreteCoder, UniformCoder, quantize_freqs
from .delayed import BlockDecoder, Slot


class BlockEncoder:
    """Collects slots for one block; models append via :meth:`add`."""

    __slots__ = ("slots",)

    def __init__(self) -> None:
        self.slots: List[Slot] = []

    def add(self, coder, sym: int) -> None:
        self.slots.append(
            Slot(k=coder.k(sym), code_for=lambda a, c=coder, s=sym: c.code_for(s, a))
        )


_RAW64 = UniformCoder(TOTAL)  # raw 16-bit payload slot
_BYTE = UniformCoder(256)
_DIGIT10 = UniformCoder(10)

#: Longest run of ASCII digits encoded through the digit path.  Bounded so
#: the per-token length symbol fits one small DiscreteCoder alphabet.
MAX_DIGIT_LEN = 16

#: Distinct-value cap for per-position word stats (StringModel.pos_words).
_POS_WORD_CAP = 64


def _is_digit_token(tok: str) -> bool:
    """True iff ``tok`` is a non-empty run of ASCII ``0-9`` we digit-encode.

    The rule is *value-only* and deterministic: any such token always takes
    the digit path (never the word dictionary), so the scalar encoder, the
    slot-plan compiler, and conformance checks agree without coordination.
    """
    return 0 < len(tok) <= MAX_DIGIT_LEN and all("0" <= c <= "9" for c in tok)


def _encode_raw_bytes(enc: BlockEncoder, payload: bytes) -> None:
    if len(payload) > 255:
        raise ValueError("escape payload too long (>255 bytes)")
    enc.add(_BYTE, len(payload))
    for b in payload:
        enc.add(_BYTE, b)


def _decode_raw_bytes(dec: BlockDecoder) -> bytes:
    n = dec.next_symbol(_BYTE)
    return bytes(dec.next_symbol(_BYTE) for _ in range(n))


def _encode_f64(enc: BlockEncoder, v: float) -> None:
    bits = int(np.float64(v).view(np.uint64))
    for i in range(4):
        enc.add(_RAW64, (bits >> (16 * i)) & 0xFFFF)


def _decode_f64(dec: BlockDecoder) -> float:
    bits = 0
    for i in range(4):
        bits |= dec.next_symbol(_RAW64) << (16 * i)
    return float(np.uint64(bits).view(np.float64))


# ---------------------------------------------------------------------------
# Categorical model (§4.1)
# ---------------------------------------------------------------------------

class CategoricalModel:
    """Frequency model over observed values + escape for unseen ones."""

    def __init__(
        self,
        values: Sequence[Any],
        esc_weight: float | None = None,
        digit_esc_weight: float | None = None,
    ) -> None:
        counts = Counter(values)
        self.id2value = list(counts.keys())
        self.value2id = {v: i for i, v in enumerate(self.id2value)}
        n = len(self.id2value)
        freqs = np.array([counts[v] for v in self.id2value], dtype=np.float64)
        if esc_weight is None:
            # Good-Turing flavour: escape mass ~ number of singletons.
            esc_weight = max(1.0, float((freqs == 1).sum()))
        self.esc = n
        # Optional second escape used by StringModel for all-digit tokens:
        # the caller owns what follows the symbol in the stream.
        self.esc_digits: int | None = None
        tail = [esc_weight]
        if digit_esc_weight is not None:
            self.esc_digits = n + 1
            tail.append(digit_esc_weight)
        self.coder = DiscreteCoder(quantize_freqs(np.append(freqs, tail)))
        self._probs = self.coder.tables.k_of.astype(np.float64) / TOTAL

    def encode_value(self, v: Any, enc: BlockEncoder, ctx=None) -> None:
        i = self.value2id.get(v)
        if i is None:
            enc.add(self.coder, self.esc)
            _encode_raw_bytes(enc, _to_bytes(v))
        else:
            enc.add(self.coder, i)

    def decode_value(self, dec: BlockDecoder, ctx=None) -> Any:
        sym = dec.next_symbol(self.coder)
        if sym == self.esc:
            return _from_bytes(_decode_raw_bytes(dec))
        return self.id2value[sym]

    def est_bits(self, v: Any) -> float:
        i = self.value2id.get(v)
        if i is None:
            return -math.log2(self._probs[self.esc]) + 8.0 * (len(_to_bytes(v)) + 1)
        return -math.log2(self._probs[i])

    def model_bytes(self) -> int:
        t = self.coder.tables
        return (t.threshold.nbytes + t.sym_u.nbytes + t.sym_v.nbytes +
                t.ja.nbytes + t.jb.nbytes + t.k_of.nbytes +
                sum(len(_to_bytes(v)) + 8 for v in self.id2value))


def _to_bytes(v: Any) -> bytes:
    """Type-tagged escape payload (unseen values keep their exact type)."""
    if isinstance(v, bytes):
        return b"B" + v
    if isinstance(v, str):
        return b"S" + v.encode("utf-8")
    if isinstance(v, (int, np.integer)):
        return b"I" + repr(int(v)).encode()
    if isinstance(v, (float, np.floating)):
        return b"F" + np.float64(v).tobytes()
    return b"S" + repr(v).encode("utf-8")


def _from_bytes(b: bytes) -> Any:
    tag, payload = b[:1], b[1:]
    if tag == b"B":
        return payload
    if tag == b"I":
        return int(payload.decode())
    if tag == b"F":
        return float(np.frombuffer(payload, np.float64)[0])
    return payload.decode("utf-8", errors="replace")


# ---------------------------------------------------------------------------
# Two-level numeric model (§4.2)
# ---------------------------------------------------------------------------

class NumericModel:
    """Two-level quantization: skew-aware buckets + uniform precision grid.

    Level 1 assigns frequency-proportional intervals to ``T`` equi-width
    buckets; level 2 splits each bucket into ``G`` equal segments of width
    <= precision ``p``.  Values are recovered to within ``p/2``; integer
    columns (``p=1``, ``integer=True``) are recovered exactly.  Out-of-range
    values escape to a raw float64 payload (the paper's bisection fallback
    carries the same cost model).
    """

    ESC_NAME = "<esc>"

    def __init__(
        self,
        values: Sequence[float],
        precision: float = 1.0,
        T: int = 512,
        integer: bool = False,
    ):
        vals = np.asarray([v for v in values], dtype=np.float64)
        if vals.size == 0:
            vals = np.zeros(1)
        self.p = float(precision)
        self.integer = bool(integer)
        self.vmin = float(
            np.floor(vals.min() / self.p) * self.p
        ) if self.integer else float(vals.min())
        vmax = float(vals.max())
        total_steps = int(math.floor((vmax - self.vmin) / self.p + 1e-9)) + 1
        self.total_steps = total_steps
        self.G = max(1, -(-total_steps // T))        # steps per bucket
        self.T = -(-total_steps // self.G)           # actual bucket count
        q = self._quantize(vals)
        buckets = np.clip(q // self.G, 0, self.T - 1)
        counts = np.bincount(buckets, minlength=self.T).astype(np.float64)
        counts = np.append(counts, max(1.0, 0.001 * vals.size))  # escape bucket
        self.esc = self.T
        self.l1 = DiscreteCoder(quantize_freqs(counts))
        self._probs = self.l1.tables.k_of.astype(np.float64) / TOTAL
        # level-2 digit chain, most-significant first
        self.l2: List[UniformCoder] = []
        g = self.G
        digits = []
        while g > 1:
            digits.append(min(g, TOTAL))
            g = -(-g // TOTAL)
        for arity in reversed(digits):
            self.l2.append(UniformCoder(arity))
        # radix weights for digit (de)composition
        self.radix = []
        w = 1
        for c in reversed(self.l2):
            self.radix.insert(0, w)
            w *= c.G

    def _quantize(self, v) -> np.ndarray:
        return np.floor(
            (np.asarray(v, dtype=np.float64) - self.vmin) / self.p + 1e-9
        ).astype(np.int64)

    def encode_value(self, v: float, enc: BlockEncoder, ctx=None) -> None:
        fv = float(v)
        q = int(self._quantize(fv)) if math.isfinite(fv) else -1
        if not (0 <= q < self.total_steps):
            enc.add(self.l1, self.esc)
            _encode_f64(enc, fv)
            return
        i, j = q // self.G, q % self.G
        enc.add(self.l1, i)
        for coder, w in zip(self.l2, self.radix):
            d = j // w
            j -= d * w
            enc.add(coder, d)

    def decode_value(
        self, dec: BlockDecoder, ctx: Optional[Dict[str, Any]] = None
    ) -> Any:
        i = dec.next_symbol(self.l1)
        if i == self.esc:
            v = _decode_f64(dec)
            return int(v) if self.integer else v
        j = 0
        for coder, w in zip(self.l2, self.radix):
            j += dec.next_symbol(coder) * w
        q = i * self.G + j
        if self.integer:
            return int(round(self.vmin + q * self.p))
        return self.vmin + (q + 0.5) * self.p

    def roundtrip(self, v: float) -> float:
        """The value the decoder will reconstruct for input ``v``."""
        q = int(self._quantize(v))
        if not (0 <= q < self.total_steps):
            return int(v) if self.integer else float(np.float64(v))
        if self.integer:
            return int(round(self.vmin + q * self.p))
        return self.vmin + (q + 0.5) * self.p

    def bucket_of(self, v: float) -> int:
        fv = float(v)
        if not math.isfinite(fv):
            return self.esc
        q = int(self._quantize(fv))
        if not (0 <= q < self.total_steps):
            return self.esc
        return q // self.G

    def est_bits(self, v: float) -> float:
        b = self.bucket_of(v)
        if b == self.esc:
            return -math.log2(self._probs[self.esc]) + 64.0
        return -math.log2(self._probs[b]) + math.log2(self.G)

    def model_bytes(self) -> int:
        t = self.l1.tables
        return (t.threshold.nbytes + t.sym_u.nbytes + t.sym_v.nbytes +
                t.ja.nbytes + t.jb.nbytes + t.k_of.nbytes + 64)


# ---------------------------------------------------------------------------
# Markov letter model (order-1 over bytes; §4.3 / App. E.2)
# ---------------------------------------------------------------------------

class ByteMarkov:
    """Order-1 byte model with END symbol; lazily built per-state coders."""

    START, END = 256, 256  # state 256 = start-of-word; symbol 256 = end

    def __init__(
        self, words: Sequence[bytes], smoothing: float = 0.1
    ) -> None:
        trans: Dict[int, Counter] = {}
        for w in words:
            prev = self.START
            for b in w:
                trans.setdefault(prev, Counter())[b] += 1
                prev = b
            trans.setdefault(prev, Counter())[self.END] += 1
        self._counts = trans
        self._smooth = smoothing
        self._coders: Dict[int, DiscreteCoder] = {}
        marg = Counter()
        for c in trans.values():
            marg.update(c)
        self._marginal_counts = marg

    def _coder(self, state: int) -> DiscreteCoder:
        c = self._coders.get(state)
        if c is None:
            cnt = self._counts.get(state, self._marginal_counts)
            freqs = np.full(257, self._smooth, dtype=np.float64)
            for b, n in cnt.items():
                freqs[b] += n
            c = DiscreteCoder(quantize_freqs(freqs))
            self._coders[state] = c
        return c

    def encode_word(self, w: bytes, enc: BlockEncoder) -> None:
        prev = self.START
        for b in w:
            enc.add(self._coder(prev), b)
            prev = b
        enc.add(self._coder(prev), self.END)

    def decode_word(self, dec: BlockDecoder) -> bytes:
        out = bytearray()
        prev = self.START
        while True:
            b = dec.next_symbol(self._coder(prev))
            if b == self.END:
                return bytes(out)
            out.append(b)
            prev = b

    def model_bytes(self) -> int:
        return sum(len(c) * 12 for c in self._counts.values())


# ---------------------------------------------------------------------------
# String model (§4.3, Figure 6)
# ---------------------------------------------------------------------------

_DELIMS = " ,.-_/:;@#|()"


class StringModel:
    """Prefix queue + word/delimiter split + global dictionary + Markov.

    The prefix queue holds the last ``K`` strings *within the current block*
    (granularity = the compression block, so random access stays closed).
    """

    K = 4
    MIN_PREFIX = 4

    def __init__(
        self,
        values: Sequence[str],
        dict_min_count: int = 2,
        dict_cap: int = 4096,
        block_tuples: int = 1,
    ):
        values = [v if isinstance(v, str) else str(v) for v in values]
        # Simulate the queue with the SAME block structure used at encode
        # time (the queue resets per block for random access): otherwise the
        # fitted (i, h, n_words) distributions mismatch reality and common
        # cases become expensive.
        queue: deque = deque(maxlen=self.K)
        i_seen, h_seen = [], []
        words_all: List[bytes] = []
        delims: List[str] = []
        nseg: List[int] = []
        digit_lens: List[int] = []
        # Per-(segment-count, word-position) token-kind stats: Counter keys
        # are a digit length L >= 1 or -1 for a dictionary/Markov word.  The
        # slot-plan compiler uses the majority kind to fix each template
        # position's mode (plan.py).
        self.pos_kinds: Dict[int, List[Counter]] = {}
        # Per-position word-value stats for non-digit tokens, capped at
        # ``_POS_WORD_CAP`` distinct values (a ``None`` key marks the
        # position as high-cardinality).  Lets the plan compiler detect
        # near-constant word positions and lower them to a vectorized
        # character-matrix check.
        self.pos_words: Dict[int, List[Counter]] = {}
        # Joint template histogram: (word count, digit-or-word flag at each
        # word position) per row.  The slot-plan compiler lowers the most
        # frequent templates to a family of plans (plan.py), since rows of
        # one template share one fixed symbol layout.
        self.templates: Counter = Counter()
        for idx, s in enumerate(values):
            if idx % max(1, block_tuples) == 0:
                queue.clear()
            i, h = self._best_match(s, queue)
            i_seen.append(i)
            if i < self.K:
                h_seen.append(h)
                rest = s[h:]
            else:
                rest = s
            segs = self._split(rest)
            row_n = (len(segs) + 1) // 2
            nseg.append(row_n)
            kinds_row = self.pos_kinds.setdefault(
                row_n, [Counter() for _ in range(row_n)]
            )
            words_row = self.pos_words.setdefault(
                row_n, [Counter() for _ in range(row_n)]
            )
            self.templates[
                (row_n, tuple(_is_digit_token(tok) for tok in segs[::2]))
            ] += 1
            for t, tok in enumerate(segs):
                if t % 2 == 0:
                    if _is_digit_token(tok):
                        digit_lens.append(len(tok))
                        kinds_row[t // 2][len(tok)] += 1
                    else:
                        words_all.append(tok.encode("utf-8"))
                        kinds_row[t // 2][-1] += 1
                        wcounter = words_row[t // 2]
                        if None in wcounter or len(wcounter) > _POS_WORD_CAP:
                            wcounter[None] += 1
                        else:
                            wcounter[tok] += 1
                else:
                    delims.append(tok)
            queue.append(s)
        # Segment-count histogram: the slot-plan compiler (plan.py) uses it
        # to derive a fixed word/delimiter template for format-fixed columns.
        self.n_words_counts = Counter(nseg)
        # Per-(segment-count, word-position) digit cap: the max digit-token
        # length observed there at fit (0 = never a digit).  The digit path
        # pads every token to the position's cap so each position costs a
        # FIXED number of symbols — what lets the slot plan lower
        # variable-length numbers (street/sku/phone runs) to fixed slots
        # while staying bit-identical to this scalar coder.
        self.pos_digit_max: Dict[int, List[int]] = {
            W: [max((k for k in c if k >= 1), default=0) for c in counters]
            for W, counters in self.pos_kinds.items()
        }
        self.i_model = DiscreteCoder(
            quantize_freqs(np.bincount(i_seen, minlength=self.K + 1) + 0.5)
        )
        self.h_model = NumericModel(
            h_seen or [self.MIN_PREFIX], precision=1, T=256, integer=True
        )
        self.n_model = NumericModel(nseg or [1], precision=1, T=64, integer=True)
        self.delim_model = CategoricalModel(delims or [" "])
        # All-digit tokens never enter the dictionary or the Markov escape:
        # they flow through the fixed-rate digit path behind ``esc_digits``.
        lens_arr = np.array([L - 1 for L in digit_lens], dtype=np.int64)
        self.digit_len_model = DiscreteCoder(
            quantize_freqs(np.bincount(lens_arr, minlength=MAX_DIGIT_LEN) + 0.5)
        )
        wc = Counter(words_all)
        common = {w for w, c in wc.most_common(dict_cap) if c >= dict_min_count}
        self.dict_model = CategoricalModel(
            [w for w in words_all if w in common] or [b""],
            esc_weight=max(1.0, sum(c for w, c in wc.items() if w not in common)),
            digit_esc_weight=max(1.0, float(len(digit_lens))),
        )
        self.markov = ByteMarkov([w for w in words_all if w not in common] or [b"a"])
        self._block_queue: deque = deque(maxlen=self.K)

    @staticmethod
    def _split(s: str) -> List[str]:
        segs: List[str] = []
        cur = []
        for ch in s:
            if ch in _DELIMS:
                segs.append("".join(cur))
                segs.append(ch)
                cur = []
            else:
                cur.append(ch)
        segs.append("".join(cur))
        return segs  # words at even idx, delimiters at odd idx

    def _best_match(self, s: str, queue) -> tuple:
        best_i, best_h = self.K, 0
        for i, prev in enumerate(queue):
            h = 0
            for a, b in zip(s, prev):
                if a != b:
                    break
                h += 1
            if h >= self.MIN_PREFIX and h > best_h:
                best_i, best_h = i, h
        return best_i, best_h

    def reset_block(self) -> None:
        self._block_queue.clear()

    def digit_cap(self, n_words: int, t: int) -> int:
        """Digit-slot budget for word position ``t`` of an ``n_words``
        template (0 = the position never digit-encodes)."""
        caps = self.pos_digit_max.get(n_words)
        if caps is None or t >= len(caps):
            return 0
        return caps[t]

    def encode_value(self, v: str, enc: BlockEncoder, ctx=None) -> None:
        s = v if isinstance(v, str) else str(v)
        i, h = self._best_match(s, self._block_queue)
        enc.add(self.i_model, i)
        if i < self.K:
            self.h_model.encode_value(h, enc)
            rest = s[h:]
        else:
            rest = s
        segs = self._split(rest)
        n_words = (len(segs) + 1) // 2
        self.n_model.encode_value(n_words, enc)
        for t, tok in enumerate(segs):
            if t % 2 == 0:
                cap = self.digit_cap(n_words, t // 2) if _is_digit_token(tok) else 0
                if 0 < len(tok) <= cap:
                    enc.add(self.dict_model.coder, self.dict_model.esc_digits)
                    enc.add(self.digit_len_model, len(tok) - 1)
                    for ch in tok:
                        enc.add(_DIGIT10, ord(ch) - 48)
                    for _ in range(cap - len(tok)):  # pad to the fixed cap
                        enc.add(_DIGIT10, 0)
                    continue
                # digit tokens longer than the position's cap (or at
                # positions never seen as digits) take the word path and
                # escape through the Markov coder — dicts never hold them.
                wb = tok.encode("utf-8")
                wid = self.dict_model.value2id.get(wb)
                if wid is None:
                    enc.add(self.dict_model.coder, self.dict_model.esc)
                    self.markov.encode_word(wb, enc)
                else:
                    enc.add(self.dict_model.coder, wid)
            else:
                self.delim_model.encode_value(tok, enc)
        self._block_queue.append(s)

    def decode_value(self, dec: BlockDecoder, ctx=None) -> str:
        i = dec.next_symbol(self.i_model)
        prefix = ""
        if i < self.K:
            h = self.h_model.decode_value(dec)
            prefix = self._block_queue[i][:h]
        n_words = self.n_model.decode_value(dec)
        parts: List[str] = []
        for t in range(n_words):
            sym = dec.next_symbol(self.dict_model.coder)
            if sym == self.dict_model.esc:
                parts.append(
                    self.markov.decode_word(dec).decode("utf-8", errors="replace")
                )
            elif sym == self.dict_model.esc_digits:
                n_dig = dec.next_symbol(self.digit_len_model) + 1
                parts.append(
                    "".join(chr(48 + dec.next_symbol(_DIGIT10))
                            for _ in range(n_dig))
                )
                for _ in range(self.digit_cap(n_words, t) - n_dig):
                    dec.next_symbol(_DIGIT10)  # drain the cap padding
            else:
                parts.append(
                    self.dict_model.id2value[sym].decode("utf-8", errors="replace")
                )
            if t < n_words - 1:
                parts.append(self.delim_model.decode_value(dec))
        s = prefix + "".join(parts)
        self._block_queue.append(s)
        return s

    def est_bits(self, v: str) -> float:
        # crude: dictionary words cheap, escapes pay per byte
        s = v if isinstance(v, str) else str(v)
        bits = 4.0
        segs = self._split(s)
        nw = (len(segs) + 1) // 2
        for t, tok in enumerate(segs):
            if t % 2 == 0:
                if _is_digit_token(tok):
                    cap = self.digit_cap(nw, t // 2)
                    if 0 < len(tok) <= cap:
                        bits += 2.0 + math.log2(10.0) * cap
                        continue
                wb = tok.encode("utf-8")
                if wb in self.dict_model.value2id:
                    bits += self.dict_model.est_bits(wb)
                else:
                    bits += 5.0 * (len(wb) + 1)
            else:
                bits += self.delim_model.est_bits(tok)
        return bits

    def model_bytes(self) -> int:
        t = self.digit_len_model.tables
        return (self.dict_model.model_bytes() + self.delim_model.model_bytes() +
                self.markov.model_bytes() + self.h_model.model_bytes() +
                self.n_model.model_bytes() + t.k_of.nbytes + 64)


# ---------------------------------------------------------------------------
# Conditional wrapper (structure learning output, §2.2/§3)
# ---------------------------------------------------------------------------

class ConditionalCategoricalModel:
    """Child categorical distribution conditioned on a parent column's value.

    Implemented as the paper describes: an unordered map from each parent
    value to a probability distribution; unseen parent values fall back to
    the marginal model.
    """

    def __init__(
        self,
        pairs: Sequence,
        parent_name: str,
        min_group: int = 8,
        max_groups: int = 4096,
    ):
        self.parent = parent_name
        values = [v for _, v in pairs]
        self.marginal = CategoricalModel(values)
        groups: Dict[Any, List[Any]] = {}
        for pv, v in pairs:
            groups.setdefault(pv, []).append(v)
        self.cond: Dict[Any, CategoricalModel] = {}
        if len(groups) <= max_groups:
            for pv, vs in groups.items():
                if len(vs) >= min_group:
                    self.cond[pv] = CategoricalModel(vs)

    def _model(self, ctx) -> CategoricalModel:
        pv = ctx.get(self.parent) if ctx else None
        return self.cond.get(pv, self.marginal)

    def encode_value(
        self, v: Any, enc: Any, ctx: Optional[Dict[str, Any]] = None
    ) -> None:
        self._model(ctx).encode_value(v, enc)

    def decode_value(
        self, dec: Any, ctx: Optional[Dict[str, Any]] = None
    ) -> Any:
        return self._model(ctx).decode_value(dec)

    def est_bits(self, v) -> float:
        return self.marginal.est_bits(v)

    def model_bytes(self) -> int:
        return (self.marginal.model_bytes() +
                sum(m.model_bytes() for m in self.cond.values()))


# ---------------------------------------------------------------------------
# Time-series model (App. E.2): AR(1) residual wrapper
# ---------------------------------------------------------------------------

class TimeSeriesModel:
    """AR(1)-residual numeric model (ARMA family; archive mode only).

    Compresses residuals ``r_t = v_t - (c + phi * v_{t-1})`` which are more
    symmetric/less heavy-tailed than raw values (App. E.2, Table 3).  Breaks
    random access (needs the previous row), matching the paper's caveat.
    """

    def __init__(
        self, values: Sequence[float], precision: float = 1.0, T: int = 512
    ) -> None:
        v = np.asarray(values, dtype=np.float64)
        if v.size < 3:
            v = np.zeros(3)
        x, y = v[:-1], v[1:]
        vx = float(np.var(x))
        self.phi = float(np.cov(x, y, bias=True)[0, 1] / vx) if vx > 0 else 0.0
        self.c = float(y.mean() - self.phi * x.mean())
        resid = y - (self.c + self.phi * x)
        self.first = NumericModel(v[:1], precision=precision, T=T)
        self.resid = NumericModel(resid, precision=precision, T=T)
        self._prev: Optional[float] = None

    def reset_block(self) -> None:
        self._prev = None

    def encode_value(self, v: float, enc: BlockEncoder, ctx=None) -> None:
        # _prev tracks the *decoder's* reconstruction to avoid drift
        if self._prev is None:
            self.first.encode_value(v, enc)
            self._prev = float(self.first.roundtrip(v))
        else:
            r = float(v) - (self.c + self.phi * self._prev)
            self.resid.encode_value(r, enc)
            self._prev = self.c + self.phi * self._prev + float(self.resid.roundtrip(r))

    def decode_value(self, dec: BlockDecoder, ctx=None) -> float:
        if self._prev is None:
            v = self.first.decode_value(dec)
        else:
            r = self.resid.decode_value(dec)
            v = self.c + self.phi * self._prev + r
        self._prev = float(v)
        return float(v)

    def model_bytes(self) -> int:
        return self.first.model_bytes() + self.resid.model_bytes() + 16
