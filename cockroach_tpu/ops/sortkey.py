"""Order-preserving sort-key normalization: one word, one argsort.

Every comparator-shaped operator here (ORDER BY, window partitioning,
DISTINCT flags, duplicate-key join chains) used to lower to a
``jnp.lexsort`` with ``2*K+1`` operands for K keys, and XLA's variadic
sort costs ~20s of compile PER OPERAND beyond 64K rows (measured on
v5e; exec/compile.py:70). The fix is the device-side twin of the
reference's ordered key encoding (pkg/sql/rowenc, mirrored host-side
in sql/rowenc.py): encode the whole key list into fixed-width unsigned
words whose integer order IS the comparator order, then sort the words.

Per key the encoding is a ``[flag:2][value:w]`` bit field:

  value  order-preserving unsigned image of the column — sign-biased
         ints, IEEE-754 monotone-bit floats (negatives complemented,
         positives sign-flipped), dictionary-RANK for strings, with w
         taken from the dtype / dictionary size so short keys pack
         densely;
  DESC   complements the value bits within the field (order-reversing
         with NO wraparound — arithmetic negation maps INT64_MIN to
         itself);
  flag   0 = NULL ordered first, 1 = live, 2 = NULL ordered last.
         NULL rows keep their value bits, so ties inside a NULL run
         break exactly like the lexsort path (which keeps the
         underlying data as a minor key);
  dead   rows outside the selection mask force every lane to all-ones:
         live lane-0 words start with flag <= 2, so dead rows sort
         strictly last, and the full-word tie keeps them in stable row
         order.

Fields concatenate major-key-first into 64-bit lanes (left-justified;
a field may straddle a lane boundary). Sorting is LSD radix over the
lanes: one stable single-key ``argsort`` per lane, least-significant
lane first — each lowers to a <=2-operand XLA sort (key + iota), so
compile cost no longer grows with the key count. Most ORDER BY lists
fit ONE lane.

The tallies mirror ops/pallas/groupagg_large.py: they bump at TRACE
time (sorts execute inside jitted programs where host counters can't
see them) and feed the engine's ``exec.sort.*`` func-metrics.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp


class _Tally:
    """Thread-safe per-site counter (see groupagg_large._KernelTally):
    traces can run concurrently from dispatcher threads and pgwire
    sessions, so a bare ``global x; x += 1`` read-modify-write races."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}

    def bump(self, kind: str, delta: int = 1) -> None:
        with self._lock:
            self._counts[kind] = self._counts.get(kind, 0) + delta

    def value(self, kind: str | None = None) -> int:
        with self._lock:
            if kind is None:
                return sum(self._counts.values())
            return self._counts.get(kind, 0)


# per consumer site ("sort" / "topk" / "window" / "join" / "distinct"
# / "spill"); read via the engine's exec.sort.* func-metrics
NORMALIZED = _Tally()   # sorts traced through the normalized plane
FALLBACKS = _Tally()    # wanted normalization, compiled on lexsort
LANES = _Tally()        # uint64 lanes sorted by normalized sorts

_ALL_ONES = jnp.uint64(0xFFFFFFFFFFFFFFFF)


def encode_value(d, *, lut=None, width: int | None = None):
    """Order-preserving unsigned image of one column.

    Returns ``(bits, w)``: a uint64 array whose low ``w`` bits order
    exactly as SQL compares ``d`` ascending (high bits zero), or None
    when the dtype has no encoding (the caller falls back to lexsort).

    lut:   dictionary rank table (code -> sort rank); the field width
           shrinks to the dictionary size.
    width: caller-asserted width for values already in [0, 2**width)
           (e.g. dense group ids) — skips the dtype-derived bias.
    """
    if lut is not None:
        lut = jnp.asarray(lut)
        size = int(lut.shape[0])
        rank = lut[jnp.clip(d, 0, size - 1)]
        return rank.astype(jnp.uint64), max(1, (size - 1).bit_length())
    if width is not None:
        return d.astype(jnp.uint64), width
    dt = jnp.dtype(d.dtype)
    if dt == jnp.bool_:
        return d.astype(jnp.uint64), 1
    w = dt.itemsize * 8
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return d.astype(jnp.uint64), w
    if jnp.issubdtype(dt, jnp.signedinteger):
        if w == 64:
            bits = jax.lax.bitcast_convert_type(d, jnp.uint64)
            return bits ^ jnp.uint64(1 << 63), 64
        # sign bias: [-2^(w-1), 2^(w-1)) -> [0, 2^w)
        return (d.astype(jnp.int64) + (1 << (w - 1))).astype(jnp.uint64), w
    if jnp.issubdtype(dt, jnp.floating):
        if w == 64:
            # XLA:TPU has no bitcast of a float64 (its 64-bit rewrite
            # refuses the HLO), so a float64 key sorts on the lexsort
            # path, on every backend alike
            return None
        udt = jnp.dtype(f"uint{w}")
        ub = jax.lax.bitcast_convert_type(d, udt)
        sign = udt.type(1 << (w - 1))
        # IEEE-754 monotone bits: complement negatives (more negative
        # = bigger magnitude = smaller), flip the sign bit of
        # positives so they land above
        bits = jnp.where((ub & sign) != 0, ~ub, ub | sign)
        return bits.astype(jnp.uint64), w
    return None


def encode_key(d, valid, desc: bool, null_first: bool, *,
               lut=None, width: int | None = None):
    """One comparator key -> ``[flag:2][value:w]`` field pieces.

    Returns a list of (bits, width<=64) pieces (flag piece first, so a
    64-bit value never needs a 66-bit shift), or None when the dtype
    is not encodable. DESC complements the value bits only — NULLS
    FIRST/LAST stays an independent axis, exactly like sort_batch's
    separate null key.
    """
    enc = encode_value(d, lut=lut, width=width)
    if enc is None:
        return None
    bits, w = enc
    if desc:
        bits = bits ^ jnp.uint64((1 << w) - 1)
    flag = jnp.where(valid, jnp.uint64(1),
                     jnp.uint64(0) if null_first else jnp.uint64(2))
    return [(flag, 2), (bits, w)]


def encode_keys(specs):
    """Flatten key specs into packable field pieces.

    specs: iterable of ``(d, valid, desc, null_first, lut, width)``.
    Returns the major-first (bits, width) list, or None when ANY key
    is unencodable (normalization is all-or-nothing per sort: a mixed
    word would not be comparator-ordered).
    """
    fields = []
    for d, valid, desc, null_first, lut, width in specs:
        f = encode_key(d, valid, desc, null_first, lut=lut, width=width)
        if f is None:
            return None
        fields.extend(f)
    return fields


def pack_lanes(fields, n: int):
    """Pack (bits, width) pieces, major field first, into uint64 lanes
    (most-significant lane first). The concatenated bit string is
    left-justified: lane 0's top bits belong to the primary field, the
    last lane zero-pads at the bottom. Fields may straddle lane
    boundaries — LSD radix over the lanes sorts the concatenated big
    integer, so split points are arbitrary."""
    lanes = []
    acc = jnp.zeros((n,), jnp.uint64)
    used = 0
    for bits, w in fields:
        assert 0 < w <= 64, "encode_key emits pieces of <= 64 bits"
        while w:
            take = min(w, 64 - used)
            part = (bits >> (w - take)) if w > take else bits
            part = part & jnp.uint64((1 << take) - 1)
            # shift-by-64 is undefined; a full-lane piece replaces acc
            acc = part if used == 0 else (acc << take) | part
            used += take
            w -= take
            if used == 64:
                lanes.append(acc)
                acc = jnp.zeros((n,), jnp.uint64)
                used = 0
    if used:
        lanes.append(acc << (64 - used))
    if not lanes:
        lanes.append(jnp.zeros((n,), jnp.uint64))
    return lanes


def mask_dead(lanes, sel):
    """Demote dead (~sel) rows strictly below every live row: all-ones
    on every lane (live lane-0 flags are <= 2, so no collision), tied
    with each other so the stable sort keeps them in row order."""
    return [jnp.where(sel, lane, _ALL_ONES) for lane in lanes]


def merge_lanes_host(runs):
    """Host-side external-merge tail of the spill sort (exec/spill.py).

    ``runs`` is a list of numpy uint64 lane stacks, one ``[L, k_i]``
    array per device-sorted run, all with the SAME lane count and
    packed by the same key specs (lanes compare across pages of one
    table: dictionaries are shared). Returns the stable ascending
    permutation over the run concatenation. Each run is already
    sorted and runs concatenate in original row order, so the stable
    lexsort reproduces byte-for-byte the permutation one device
    sort_perm over all rows would have produced."""
    import numpy as np  # host-only tail; keep the module jax-first
    lanes = [np.concatenate([r[i] for r in runs])
             for i in range(runs[0].shape[0])]
    # np.lexsort treats its LAST key as primary; lanes are major-first
    return np.lexsort(tuple(reversed(lanes)))


def words_of(lanes, fields) -> list:
    """The lanes as 32-bit words, most significant first, without the
    zero words pack_lanes pads the last lane with."""
    total = sum(w for _, w in fields)
    words = []
    for lane in lanes:
        words += [(lane >> 32).astype(jnp.uint32), lane.astype(jnp.uint32)]
    return words[:max(1, -(-total // 32))]


def sort_perm_words(words, *, kind: str | None = None,
                    stable: bool = True):
    """An ascending permutation over 32-bit key words (the most
    significant first): one unstable sort of the words with the row
    index beside them. `stable`: the index is the last key, so no two
    rows tie and the permutation is a stable sort's; else rows equal
    on every word come in an order of XLA's choosing. XLA:TPU compiles
    a stable argsort of 2^15 rows or more in 50-90 s; this form, over
    2^20 rows (compiled here for a described v5e, PR 40): two words
    and the index as keys 32.5 s (86 s the stable argsort of a u64),
    three 55, three words and the index beside them 45, two 26."""
    if kind is not None:
        NORMALIZED.bump(kind)
        LANES.bump(kind, -(-len(words) // 2))
    n = words[0].shape[0]
    out = jax.lax.sort(tuple(words) + (jax.lax.iota(jnp.int32, n),),
                       num_keys=len(words) + int(stable),
                       is_stable=False)
    return out[-1]


def sort_perm(lanes, *, kind: str | None = None):
    """Stable ascending permutation over the packed word.

    LSD over the lanes: one stable single-key argsort each, least
    significant first; composing ``perm = perm[argsort(lane[perm])]``
    leaves the major lane's order dominant with prior lanes (and
    finally row index) breaking ties — byte-for-byte the lexsort
    contract, at <=2 sort operands per lane."""
    if kind is not None:
        NORMALIZED.bump(kind)
        LANES.bump(kind, len(lanes))
    perm = None
    for lane in reversed(lanes):
        if perm is None:
            perm = jnp.argsort(lane, stable=True)
        else:
            perm = perm[jnp.argsort(lane[perm], stable=True)]
    return perm
