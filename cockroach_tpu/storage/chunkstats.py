"""Write-time per-chunk statistics (PR 9, ISSUE 9 tentpole a).

Zone maps used to be computed lazily on first scan; this module moves
summary construction to chunk SEAL time and adds two new per-chunk
summaries, the way Taurus-style NDP pushes statistics maintenance to
the write path so the read path only consults them:

- **zones** — per-column (lo, hi, null_count, valid_count), the same
  tuple `Chunk.zone` always served, but precomputed for every column
  at seal/compaction instead of on demand.
- **blocked bloom filters** — over int-family columns (which includes
  dict-coded string columns: their chunk arrays hold int32 codes).
  One cache line (a uint64 word) per key block; 4 bits per key. Used
  by join-induced skipping to reject chunks whose key range overlaps
  a semi-join filter but whose actual key set does not.
- **distinct-count sketch** — a 256-register HLL-style estimator per
  column, mergeable by register max; sizes the exact-keys vs bloom
  decision when a semi-join filter is derived from a build side.

MVCC window: `ts_min` is exact forever (mvcc_ts is immutable after
seal). `del_max` is the max mvcc_del AT SEAL TIME — tombstones only
ever LOWER mvcc_del (a live row's sentinel becomes a finite deletion
timestamp, never the reverse), so the sealed value stays a valid
upper bound without any post-seal invalidation. A chunk is invisible
at read_ts when ts_min > read_ts (everything born later) or
del_max <= read_ts (everything dead by then).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

# fnv/murmur-style 64-bit finalizer constants (splitmix64)
_MIX1 = np.uint64(0xFF51AFD7ED558CCD)
_MIX2 = np.uint64(0xC4CEB9FE1A85EC53)
_S33 = np.uint64(33)
_U6 = np.uint64(6)
_U63 = np.uint64(63)


def mix64(keys: np.ndarray, work: "Workspace | None" = None) -> np.ndarray:
    """Vectorized 64-bit avalanche over int-family keys (splitmix64
    finalizer). Every int width hashes its sign-extended value
    identically. With `work` the digests are its `hashes` buffer."""
    work = work or Workspace()
    n = len(keys)
    h, (t, _) = work.hashes(n), work.pair(n)
    np.copyto(h.view(np.int64), keys, casting="unsafe")
    for mult in (_MIX1, _MIX2, None):
        np.right_shift(h, _S33, out=t)
        np.bitwise_xor(h, t, out=h)
        if mult is not None:
            np.multiply(h, mult, out=h)
    return h


class Workspace:
    """What the summaries of one chunk after another are built in:
    three uint64 buffers of a chunk's rows (`hashes`, a column's
    digests; `pair`, two the hash, the bloom and the sketch work in)
    and the bloom's `flags`. A fresh array is paid for in page faults
    when first written, which on a bulk ingest is most of the cost of
    a summary, and threads that allocate queue on the allocator; a
    thread that keeps one workspace pays once."""

    def __init__(self):
        self._words = np.empty((3, 0), dtype=np.uint64)
        self._flags = np.empty(0, dtype=bool)

    def _rows(self, n: int) -> np.ndarray:
        if self._words.shape[1] < n:
            self._words = np.empty((3, n), dtype=np.uint64)
        return self._words[:, :n]

    def hashes(self, n: int) -> np.ndarray:
        return self._rows(n)[0]

    def pair(self, n: int) -> tuple:
        rows = self._rows(n)
        return rows[1], rows[2]

    def flags(self, n: int) -> np.ndarray:
        if len(self._flags) < n:
            self._flags = np.empty(n, dtype=bool)
        return self._flags[:n]


def _next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


class BlockedBloom:
    """Register-blocked bloom filter: each key sets 4 bits inside ONE
    uint64 word, so a membership probe touches a single cache line.
    Sized at ~8 keys/word (~2% false positives); never false-negative.
    Serializes to the raw word array (`tobytes`/`from_bytes`) so a
    semi-join filter can ship as a compact wire frame."""

    __slots__ = ("words",)

    def __init__(self, n_keys: int = 0, words: np.ndarray | None = None):
        if words is not None:
            self.words = words
        else:
            n = _next_pow2(max(8, (int(n_keys) + 7) // 8))
            self.words = np.zeros(n, dtype=np.uint64)

    def add(self, keys: np.ndarray) -> None:
        if len(keys):
            self.add_hashed(mix64(keys))

    def add_hashed(self, h: np.ndarray,
                   work: Workspace | None = None) -> None:
        """Insert pre-hashed keys (seal-time stats hash each column
        once and feed the same digest to bloom and sketch). A key's
        four bits are set by four plain scatters into a flag a bit,
        packed back into the words: unlike a read-modify-write a key
        (`bitwise_or.at`) a scatter runs outside the interpreter lock,
        so chunks summarized side by side do not queue."""
        if len(h) == 0:
            return
        work = work or Workspace()
        nw = len(self.words)
        t, base = work.pair(len(h))
        bits = work.flags(nw * 64)
        if self.words.any():
            bits[:] = np.unpackbits(self.words.view(np.uint8),
                                    bitorder="little")
        else:
            bits[:] = False
        np.bitwise_and(h, np.uint64(nw - 1), out=base)   # the key's word
        np.left_shift(base, _U6, out=base)
        for shift in self._SHIFTS:
            np.right_shift(h, shift, out=t)
            np.bitwise_and(t, _U63, out=t)
            np.bitwise_or(t, base, out=t)
            bits[t.view(np.int64)] = True
        self.words = np.packbits(bits, bitorder="little").view(np.uint64)

    def might_contain(self, keys: np.ndarray) -> np.ndarray:
        """Boolean array: False is definite absence."""
        if len(keys) == 0:
            return np.zeros(0, dtype=bool)
        h = mix64(keys)
        block = (h & np.uint64(len(self.words) - 1)).astype(np.int64)
        m = self._masks(h)
        return (self.words[block] & m) == m

    def might_contain_any(self, keys: np.ndarray) -> bool:
        return bool(self.might_contain(keys).any())

    # a key's four bit positions inside its word: bits 32.., 38..,
    # 44.. and 50.. of its hash, six each
    _SHIFTS = tuple(np.uint64(x) for x in (32, 38, 44, 50))

    @classmethod
    def _masks(cls, h: np.ndarray) -> np.ndarray:
        one = np.uint64(1)
        m = np.zeros(len(h), dtype=np.uint64)
        for shift in cls._SHIFTS:
            m |= one << ((h >> shift) & _U63)
        return m

    def tobytes(self) -> bytes:
        return self.words.tobytes()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "BlockedBloom":
        return cls(words=np.frombuffer(raw, dtype=np.uint64).copy())


class DistinctSketch:
    """256-register HLL-style distinct estimator. Registers hold the
    max leading-zero rank of the low 56 hash bits per bucket; two
    sketches over disjoint row sets merge by elementwise max (the
    compaction story: rebuilt chunks re-sketch, table-level estimates
    merge)."""

    __slots__ = ("regs",)
    _M = 256

    def __init__(self, regs: np.ndarray | None = None):
        self.regs = (regs if regs is not None
                     else np.zeros(self._M, dtype=np.uint8))

    def add(self, keys: np.ndarray) -> None:
        if len(keys):
            self.add_hashed(mix64(keys))

    def add_hashed(self, h: np.ndarray,
                   work: Workspace | None = None) -> None:
        n = len(h)
        if n == 0:
            return
        t, key = (work or Workspace()).pair(n)
        # rank = leading zeros of the 56-bit suffix, + 1. frexp's
        # exponent of a positive float is its bit length (0 for 0)
        np.bitwise_and(h, np.uint64((1 << 56) - 1), out=t)
        low = key.view(np.float64)
        np.copyto(low, t, casting="unsafe")
        rho = t.view(np.int32)[:n]
        np.frexp(low, out=(low, rho))
        np.subtract(57, rho, out=rho)
        # the max rank a register, without a read-modify-write a key:
        # mark every (register, rank) pair that occurs (a plain
        # scatter), then the highest marked rank of each register
        key = key.view(np.int64)
        np.right_shift(h, np.uint64(56), out=key.view(np.uint64))
        np.left_shift(key, 6, out=key)
        np.bitwise_or(key, rho, out=key)
        seen = np.zeros(self._M * 64, dtype=bool)
        seen[key] = True
        top = (seen.reshape(self._M, 64)
               * np.arange(64, dtype=np.uint8)).max(axis=1)
        np.maximum(self.regs, top, out=self.regs)

    def merge(self, other: "DistinctSketch") -> None:
        np.maximum(self.regs, other.regs, out=self.regs)

    def estimate(self) -> int:
        m = float(self._M)
        regs = self.regs.astype(np.float64)
        est = (0.7213 / (1 + 1.079 / m)) * m * m \
            / np.sum(np.exp2(-regs))
        zeros = int(np.count_nonzero(self.regs == 0))
        if est <= 2.5 * m and zeros:
            est = m * np.log(m / zeros)       # linear counting
        return int(round(est))


@dataclass
class ChunkStats:
    """Everything a chunk precomputes at seal: zone tuples for every
    data column, blooms + distinct sketches for int-family columns,
    and the MVCC visibility window."""

    zones: dict = field(default_factory=dict)
    blooms: dict = field(default_factory=dict)
    distinct: dict = field(default_factory=dict)
    ts_min: int = 0
    del_max: int = 0


def column_zone(vals: np.ndarray, valid: np.ndarray):
    """(lo, hi, null_count, valid_count) for one column; None bounds
    when the dtype is unordered (object) or no valid row exists —
    byte-identical to the historical lazy `Chunk.zone` result."""
    nvalid = int(valid.sum())
    nulls = len(valid) - nvalid
    if vals.dtype.kind not in "biuf" or nvalid == 0:
        return (None, None, nulls, nvalid)
    vv = vals if nvalid == len(vals) else vals[valid]
    lo, hi = vv.min(), vv.max()
    if vals.dtype.kind == "f":
        if np.isnan(lo) or np.isnan(hi):
            return (None, None, nulls, nvalid)
        return (float(lo), float(hi), nulls, nvalid)
    return (int(lo), int(hi), nulls, nvalid)


def compute(data: dict, valid: dict, mvcc_ts: np.ndarray,
            mvcc_del: np.ndarray,
            work: Workspace | None = None) -> ChunkStats:
    """Build the full seal-time summary for one chunk. Blooms and
    sketches cover int-family columns only (ints + dict codes); float
    and object columns still get zones. `work`: the buffers to work
    in, for a caller that summarizes many chunks (compute_many)."""
    st = ChunkStats()
    n = len(mvcc_ts)
    work = work or Workspace()
    for col, vals in data.items():
        v = valid[col]
        z = column_zone(vals, v)
        st.zones[col] = z
        if vals.dtype.kind in "iu" and vals.dtype.itemsize >= 2:
            # z[3] is the valid count: reuse it to skip the boolean
            # gather on fully-valid columns, and hash once for both
            # summaries — this runs on every ingest/compaction seal
            keys = vals if z[3] == len(vals) else vals[v]
            h = mix64(keys, work)
            bl = BlockedBloom(len(keys))
            bl.add_hashed(h, work)
            st.blooms[col] = bl
            sk = DistinctSketch()
            sk.add_hashed(h, work)
            st.distinct[col] = sk
    st.ts_min = int(mvcc_ts.min()) if n else 0
    st.del_max = int(mvcc_del.max()) if n else 0
    return st


# threads that build chunk summaries side by side on a bulk ingest:
# numpy gives up the interpreter lock inside its loops, so they scale
# until the memory bus is full
MAX_WORKERS = 16


def compute_many(chunks: list) -> list:
    """compute() for each (data, valid, mvcc_ts, mvcc_del), in order.
    More than one chunk is spread over a thread pool, each thread with
    a Workspace of its own."""
    workers = min(len(chunks), os.cpu_count() or 1, MAX_WORKERS)
    if workers <= 1:
        return [compute(*c) for c in chunks]
    local = threading.local()

    def one(chunk):
        if not hasattr(local, "work"):
            local.work = Workspace()
        return compute(*chunk, work=local.work)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(one, chunks))


def extend(st: ChunkStats, col: str, vals: np.ndarray,
           valid: np.ndarray) -> None:
    """Add one column's summaries to existing stats (backfill of a
    new column into an already-sealed chunk)."""
    st.zones[col] = column_zone(vals, valid)
    if vals.dtype.kind in "iu" and vals.dtype.itemsize >= 2:
        keys = vals[valid]
        bl = BlockedBloom(len(keys))
        bl.add(keys)
        st.blooms[col] = bl
        sk = DistinctSketch()
        sk.add(keys)
        st.distinct[col] = sk
