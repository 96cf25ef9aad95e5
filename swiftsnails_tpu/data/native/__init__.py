"""ctypes bindings for the native data-pipeline core (libsnails.cpp).

Compiled on demand with g++ (no pybind11 — plain C ABI + ctypes, per the
environment's binding guidance) next to the source, under a name keyed on
the source's content, so a fresh copy of the tree (whose mtimes say nothing)
never loads a library built from other source. ``*.so`` stays out of git.

Every entry point has a pure-Python twin in :mod:`swiftsnails_tpu.data`, at
roughly a sixth of the rate. Training picks between them with
:func:`use_native`: the native pipeline unless the config says
``use_native: 0`` — and if the library then fails to build, that is a
:class:`NativeBuildError` carrying the compiler's output, not a quiet drop
to the slow producers. :func:`available` remains for optional callers and
for tests that skip without a toolchain.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "libsnails.cpp")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None


class NativeBuildError(RuntimeError):
    """The native library could not be built or loaded."""


def _so_path() -> str:
    """Where the library for the committed source lives.

    ``SSN_NATIVE_SO`` points at an alternate build (e.g. the ASan/TSan
    builds made by tools/native_sanitize.sh); otherwise the name carries a
    digest of the source and the compile line."""
    override = os.environ.get("SSN_NATIVE_SO")
    if override:
        return override
    h = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libsnails-{h.hexdigest()[:16]}.so")


def _build(so: str) -> Optional[str]:
    """Compile ``so`` from the source if absent; returns error text or None."""
    if os.path.exists(so):
        return None
    if os.environ.get("SSN_NATIVE_SO"):
        return f"SSN_NATIVE_SO not found: {so}"
    # build under a private name, then rename: concurrent builders (replica
    # processes starting together) each publish a complete file or nothing
    tmp = f"{so[:-3]}.{os.getpid()}.tmp.so"
    cmd = _CXX + ["-o", tmp, _SRC]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{' '.join(cmd)}: invocation failed: {e}"
    if proc.returncode != 0:
        return (f"{' '.join(cmd)}: exit {proc.returncode}\n"
                f"{proc.stderr or proc.stdout}")
    os.replace(tmp, so)
    for stale in glob.glob(os.path.join(_DIR, "libsnails*.so")):
        if stale != so:
            try:
                os.remove(stale)
            except OSError:
                pass
    return None


def _load():
    global _lib, _build_error
    if _lib is not None or _build_error is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        so = _so_path()
        err = _build(so)
        if err is not None:
            _build_error = err
            return None
        lib = ctypes.CDLL(so)
        c = ctypes
        try:
            _bind(lib, c)
        except AttributeError as e:
            # e.g. SSN_NATIVE_SO pointing at a build of older source
            _build_error = f"native library missing symbols (stale build?): {e}"
            return None
        _lib = lib
        return _lib


def _bind(lib, c):
        lib.ssn_murmur64.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
        lib.ssn_hash_row.argtypes = [c.c_void_p, c.c_int64, c.c_uint64, c.c_void_p]
        lib.ssn_vocab_build.restype = c.c_void_p
        lib.ssn_vocab_build.argtypes = [c.c_char_p, c.c_int, c.c_int]
        lib.ssn_vocab_size.restype = c.c_int64
        lib.ssn_vocab_size.argtypes = [c.c_void_p]
        lib.ssn_vocab_counts.argtypes = [c.c_void_p, c.c_void_p]
        lib.ssn_vocab_word.restype = c.c_int
        lib.ssn_vocab_word.argtypes = [c.c_void_p, c.c_int64, c.c_char_p, c.c_int]
        lib.ssn_vocab_free.argtypes = [c.c_void_p]
        lib.ssn_encode.restype = c.c_int64
        lib.ssn_encode.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p, c.c_int64]
        lib.ssn_skipgram_pairs.restype = c.c_int64
        lib.ssn_skipgram_pairs.argtypes = [
            c.c_void_p, c.c_int64, c.c_int, c.c_uint64, c.c_int,
            c.c_void_p, c.c_void_p, c.c_int64,
        ]
        lib.ssn_skipgram_windows.restype = c.c_int64
        lib.ssn_skipgram_windows.argtypes = [
            c.c_void_p, c.c_int64, c.c_int, c.c_uint64, c.c_int, c.c_void_p,
        ]
        lib.ssn_subsample.restype = c.c_int64
        lib.ssn_subsample.argtypes = [
            c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
            c.c_double, c.c_double, c.c_uint64, c.c_void_p,
        ]
        lib.ssn_read_ctr.restype = c.c_int64
        lib.ssn_read_ctr.argtypes = [c.c_char_p, c.c_int, c.c_void_p, c.c_void_p, c.c_int64]
        lib.ssn_neg_table_build.restype = c.c_void_p
        lib.ssn_neg_table_build.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
        lib.ssn_neg_table_free.argtypes = [c.c_void_p]
        lib.ssn_sgns_train.restype = c.c_double
        lib.ssn_sgns_train.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int, c.c_void_p, c.c_void_p,
            c.c_int64, c.c_int, c.c_float, c.c_void_p, c.c_uint64,
        ]
        lib.ssn_prefetch_open.restype = c.c_void_p
        lib.ssn_prefetch_open.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_int, c.c_int, c.c_uint64,
        ]
        lib.ssn_prefetch_next.restype = c.c_int
        lib.ssn_prefetch_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
        lib.ssn_prefetch_close.argtypes = [c.c_void_p]
        lib.ssn_win_prefetch_open.restype = c.c_void_p
        lib.ssn_win_prefetch_open.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int, c.c_int64, c.c_int64,
            c.c_int, c.c_int, c.c_int, c.c_uint64,
        ]
        lib.ssn_win_prefetch_next.restype = c.c_int
        lib.ssn_win_prefetch_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
        lib.ssn_win_prefetch_close.argtypes = [c.c_void_p]
        lib.ssn_vocab_build_stream.restype = c.c_void_p
        lib.ssn_vocab_build_stream.argtypes = [c.c_char_p, c.c_int, c.c_int]
        lib.ssn_stream_open.restype = c.c_void_p
        lib.ssn_stream_open.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.c_int64]
        lib.ssn_stream_next.restype = c.c_int64
        lib.ssn_stream_next.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
        lib.ssn_stream_close.argtypes = [c.c_void_p]
        lib.ssn_ctr_stream_open.restype = c.c_void_p
        lib.ssn_ctr_stream_open.argtypes = [c.c_char_p, c.c_int, c.c_int64, c.c_int64]
        lib.ssn_ctr_stream_next.restype = c.c_int64
        lib.ssn_ctr_stream_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64]
        lib.ssn_ctr_stream_close.argtypes = [c.c_void_p]
        lib.ssn_tier_remap.restype = c.c_int64
        lib.ssn_tier_remap.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_void_p,
        ]
        lib.ssn_tier_clock_sweep.restype = c.c_int64
        lib.ssn_tier_clock_sweep.argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_void_p,
        ]


def available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


def _require():
    lib = _load()
    if lib is None:
        raise NativeBuildError(f"native pipeline unavailable: {_build_error}")
    return lib


def use_native(config) -> bool:
    """Whether this run takes the native pipeline. ``use_native: 0`` opts
    out; otherwise the library must build — a failed build raises
    :class:`NativeBuildError` rather than leaving a host-bound run with no
    message."""
    if not config.get_bool("use_native", True):
        return False
    _require()
    return True


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def murmur64(x: np.ndarray) -> np.ndarray:
    lib = _require()
    x = np.ascontiguousarray(x, dtype=np.uint64)
    out = np.empty_like(x)
    lib.ssn_murmur64(_ptr(x), _ptr(out), x.size)
    return out


def hash_row(keys: np.ndarray, capacity: int) -> np.ndarray:
    lib = _require()
    keys = np.ascontiguousarray(keys, dtype=np.uint32)
    out = np.empty(keys.size, dtype=np.int64)
    lib.ssn_hash_row(_ptr(keys), keys.size, capacity, _ptr(out))
    return out


class NativeVocab:
    """C++ vocab builder (reference hashmap.h + scan_file_by_line parity).

    ``stream=True`` (default) reads through a fixed buffer — O(vocab) memory
    regardless of corpus size, same ordering contract as the whole-file path.
    """

    def __init__(self, path: str, min_count: int = 5, max_size: int = 0,
                 stream: bool = True):
        lib = _require()
        self._lib = lib
        build = lib.ssn_vocab_build_stream if stream else lib.ssn_vocab_build
        self._h = build(path.encode(), min_count, max_size)
        if not self._h:
            raise OSError(f"cannot read {path}")

    def __len__(self) -> int:
        return int(self._lib.ssn_vocab_size(self._h))

    def counts(self) -> np.ndarray:
        out = np.empty(len(self), dtype=np.int64)
        self._lib.ssn_vocab_counts(self._h, _ptr(out))
        return out

    def words(self) -> List[str]:
        buf = ctypes.create_string_buffer(65536)
        out = []
        for i in range(len(self)):
            n = self._lib.ssn_vocab_word(self._h, i, buf, len(buf))
            if n < 0:
                raise ValueError(f"word {i} too long")
            out.append(buf.value.decode("utf-8", "replace"))
        return out

    def encode_file(self, path: str) -> np.ndarray:
        # Size guess: for the vocab's own source file the kept-token count is
        # exactly counts().sum(), avoiding a second full tokenize pass. For a
        # different file the guess may be short; ssn_encode then returns the
        # true count negated and we retry once with the exact size.
        guess = int(self.counts().sum()) if len(self) else 0
        out = np.empty(max(guess, 1), dtype=np.int32)
        got = self._lib.ssn_encode(self._h, path.encode(), _ptr(out), out.size)
        if got == -1:
            # -1 is unambiguously an IO error: overflow returns -(total) and
            # a 1-token corpus always fits the >=1-sized buffer
            raise OSError(f"cannot read {path}")
        if got < 0:
            needed = -got
            out = np.empty(needed, dtype=np.int32)
            got = self._lib.ssn_encode(self._h, path.encode(), _ptr(out), needed)
            if got < 0:
                raise RuntimeError("corpus changed size during encode")
        return out[:got]

    def encode_stream(self, path: str, chunk_tokens: int,
                      byte_start: int = 0, byte_end: int = 0):
        """Yield encoded int32 chunks of <= chunk_tokens ids (OOV dropped).

        Bounded memory (one read buffer + one chunk): the streaming twin of
        :meth:`encode_file` for corpora that don't fit in RAM —
        ``scan_file_by_line`` parity (src/utils/file.h:11-33). A nonzero
        ``(byte_start, byte_end)`` reads that span with Hadoop split
        semantics (a token belongs to the span its first byte falls in), the
        multi-host stdin-split equivalent.
        """
        lib = self._lib
        h = lib.ssn_stream_open(self._h, path.encode(), byte_start, byte_end)
        if not h:
            raise OSError(f"cannot read {path}")
        try:
            while True:
                out = np.empty(chunk_tokens, dtype=np.int32)
                got = lib.ssn_stream_next(h, _ptr(out), chunk_tokens)
                if got <= 0:
                    return
                yield out[:got]
        finally:
            lib.ssn_stream_close(h)

    def to_python(self):
        from swiftsnails_tpu.data.vocab import Vocab

        return Vocab(self.words(), self.counts())

    def close(self):
        if self._h:
            self._lib.ssn_vocab_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def skipgram_pairs(
    ids: np.ndarray, window: int, seed: int = 0, dynamic: bool = True
) -> Tuple[np.ndarray, np.ndarray]:
    lib = _require()
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    n = lib.ssn_skipgram_pairs(_ptr(ids), ids.size, window, seed, int(dynamic), None, None, 0)
    centers = np.empty(n, dtype=np.int32)
    contexts = np.empty(n, dtype=np.int32)
    got = lib.ssn_skipgram_pairs(
        _ptr(ids), ids.size, window, seed, int(dynamic), _ptr(centers), _ptr(contexts), n
    )
    assert got == n, (got, n)
    return centers, contexts


def skipgram_windows(
    ids: np.ndarray, window: int, seed: int = 0, dynamic: bool = True
) -> Tuple[np.ndarray, np.ndarray]:
    """Center-major window schema (centers [n], contexts [n, 2w], -1 pads).

    Same b-draw sequence as :func:`skipgram_pairs` for a given seed, so the
    flat and grouped schemas generate the identical pair set.
    """
    lib = _require()
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    ctxs = np.empty((ids.size, 2 * window), dtype=np.int32)
    got = lib.ssn_skipgram_windows(
        _ptr(ids), ids.size, window, seed, int(dynamic), _ptr(ctxs)
    )
    assert got == ids.size, (got, ids.size)
    return ids.copy(), ctxs


def subsample(
    ids: np.ndarray, counts: np.ndarray, threshold: float, seed: int = 0
) -> np.ndarray:
    lib = _require()
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(ids.size, dtype=np.int32)
    k = lib.ssn_subsample(
        _ptr(ids), ids.size, _ptr(counts), counts.size,
        float(counts.sum()), threshold, seed, _ptr(out),
    )
    return out[:k]


def read_ctr(path: str, num_fields: int) -> Tuple[np.ndarray, np.ndarray]:
    lib = _require()
    n = lib.ssn_read_ctr(path.encode(), num_fields, None, None, 0)
    if n < 0:
        raise OSError(f"cannot read {path}")
    labels = np.empty(n, dtype=np.float32)
    feats = np.empty((n, num_fields), dtype=np.int32)
    got = lib.ssn_read_ctr(path.encode(), num_fields, _ptr(labels), _ptr(feats), n)
    if got < 0:
        raise RuntimeError("file changed size during read")
    return labels[:got], feats[:got]


def read_ctr_stream(path: str, num_fields: int, rows_per_chunk: int = 1 << 20,
                    byte_start: int = 0, byte_end: int = 0):
    """Yield (labels, feats) chunks of <= rows_per_chunk parsed CTR records.

    Bounded-memory twin of :func:`read_ctr` (line carry across read-buffer
    edges) — what the Criteo-1TB-scale configs feed from. A nonzero byte
    span reads that shard with Hadoop line-split semantics.
    """
    lib = _require()
    h = lib.ssn_ctr_stream_open(path.encode(), num_fields, byte_start, byte_end)
    if not h:
        raise OSError(f"cannot read {path}")
    try:
        while True:
            labels = np.empty(rows_per_chunk, dtype=np.float32)
            feats = np.empty((rows_per_chunk, num_fields), dtype=np.int32)
            got = lib.ssn_ctr_stream_next(h, _ptr(labels), _ptr(feats), rows_per_chunk)
            if got <= 0:
                return
            yield labels[:got], feats[:got]
    finally:
        lib.ssn_ctr_stream_close(h)


def sgns_train(
    syn0: np.ndarray,
    syn1: np.ndarray,
    centers: np.ndarray,
    contexts: np.ndarray,
    counts: np.ndarray,
    negatives: int = 5,
    lr: float = 0.025,
    table_size: int = 1 << 22,
    seed: int = 0,
) -> float:
    """Run the compiled single-node SGNS worker loop in place.

    Returns elapsed seconds for the training loop (excluding the one-time
    negative-table build). ``syn0``/``syn1`` are updated in place — this is
    bench.py's calibrated per-node CPU parameter-server baseline.
    """
    lib = _require()
    # The C loop trusts its pointers; validate everything that could write
    # out of bounds (real raises, not asserts — must survive python -O).
    for name, a in (("syn0", syn0), ("syn1", syn1)):
        if a.dtype != np.float32 or not a.flags.c_contiguous or a.ndim != 2:
            raise ValueError(f"{name} must be a C-contiguous float32 matrix")
    if syn0.shape[1] != syn1.shape[1]:
        raise ValueError(f"dim mismatch: {syn0.shape} vs {syn1.shape}")
    centers = np.ascontiguousarray(centers, dtype=np.int32)
    contexts = np.ascontiguousarray(contexts, dtype=np.int32)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    if centers.shape != contexts.shape:
        raise ValueError("centers/contexts length mismatch")
    if centers.size and (
        centers.min() < 0 or centers.max() >= syn0.shape[0]
    ):
        raise ValueError("center id out of range for syn0")
    if contexts.size and (
        contexts.min() < 0 or contexts.max() >= syn1.shape[0]
    ):
        raise ValueError("context id out of range for syn1")
    # negative-table targets index syn1 rows in [0, counts.size)
    if counts.size > syn1.shape[0]:
        raise ValueError("counts longer than syn1 rows")
    table = lib.ssn_neg_table_build(_ptr(counts), counts.size, table_size)
    if not table:
        raise ValueError("empty vocab for negative table")
    try:
        return float(
            lib.ssn_sgns_train(
                _ptr(syn0), _ptr(syn1), syn0.shape[1], _ptr(centers),
                _ptr(contexts), centers.size, negatives, lr, table, seed,
            )
        )
    finally:
        lib.ssn_neg_table_free(table)


class PairPrefetcher:
    """Bounded-queue shuffled batch producer (queue_with_capacity parity).

    A C++ producer thread shuffles and slices (centers, contexts) into
    fixed-size batches; iteration blocks on the bounded queue and ends when
    the producer finishes all epochs (poison-free close semantics).
    """

    def __init__(
        self,
        centers: np.ndarray,
        contexts: np.ndarray,
        batch_size: int,
        epochs: int = 1,
        capacity: int = 8,
        seed: int = 0,
    ):
        lib = _require()
        self._lib = lib
        self.batch_size = batch_size
        c = np.ascontiguousarray(centers, dtype=np.int32)
        x = np.ascontiguousarray(contexts, dtype=np.int32)
        self._h = lib.ssn_prefetch_open(
            _ptr(c), _ptr(x), c.size, batch_size, epochs, capacity, seed
        )
        if not self._h:
            raise ValueError("bad prefetcher arguments (empty data or batch > n)")

    def __iter__(self):
        while self._h:  # guard: next() after close() must end, not segfault
            centers = np.empty(self.batch_size, dtype=np.int32)
            contexts = np.empty(self.batch_size, dtype=np.int32)
            ok = self._lib.ssn_prefetch_next(self._h, _ptr(centers), _ptr(contexts))
            if not ok:
                return
            yield {"centers": centers, "contexts": contexts}

    def close(self):
        if self._h:
            self._lib.ssn_prefetch_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class WindowPrefetcher:
    """Center-major window-batch producer (grouped/dedup kernel schema).

    C++ worker threads shuffle BLOCKS of ``block`` consecutive windows
    (``block=1`` = plain row shuffle) and assemble
    ``{"centers": [B], "contexts": [B, cw]}`` batches behind a bounded
    order-preserving ticket ring — the batch sequence is deterministic in
    ``seed``/``epochs`` regardless of worker count. This replaces the
    Python ``batch_stream``/``batch_stream_blocks`` loop in the hot path
    (same schema, native assembly).
    """

    def __init__(
        self,
        centers: np.ndarray,
        contexts: np.ndarray,
        batch_size: int,
        block: int = 1,
        epochs: int = 1,
        capacity: int = 8,
        workers: int = 0,
        seed: int = 0,
    ):
        lib = _require()
        self._lib = lib
        self.batch_size = batch_size
        # the C producer BORROWS these buffers (no copy — a [n, 2w] window
        # array is already the chunk's dominant allocation); the refs below
        # keep them alive for the handle's lifetime. Callers must not
        # mutate them while iterating.
        self._c = np.ascontiguousarray(centers, dtype=np.int32)
        self._x = np.ascontiguousarray(contexts, dtype=np.int32)
        if self._x.ndim != 2 or self._x.shape[0] != self._c.size:
            raise ValueError(f"contexts must be [n, cw], got {self._x.shape}")
        self.cw = self._x.shape[1]
        self._h = lib.ssn_win_prefetch_open(
            _ptr(self._c), _ptr(self._x), self._c.size, self.cw, batch_size,
            block, epochs, capacity, workers, seed,
        )
        if not self._h:
            raise ValueError(
                "bad window-prefetcher arguments (empty data, batch > n, or "
                "batch not a multiple of block)"
            )

    def __iter__(self):
        while self._h:  # guard: next() after close() must end, not segfault
            centers = np.empty(self.batch_size, dtype=np.int32)
            contexts = np.empty((self.batch_size, self.cw), dtype=np.int32)
            ok = self._lib.ssn_win_prefetch_next(self._h, _ptr(centers), _ptr(contexts))
            if not ok:
                return
            yield {"centers": centers, "contexts": contexts}

    def close(self):
        if self._h:
            self._lib.ssn_win_prefetch_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass



# ---------------------------------------------------------------- tiered ---


def tier_remap(slot_of: np.ndarray, rows: np.ndarray,
               group: int = 1) -> Tuple[np.ndarray, int]:
    """Master-row ids -> cache-slot ids for the tiered store's per-step remap
    (``TieredTable.remap`` hot path). Returns ``(slots, n_nonresident)``;
    the caller raises on a nonzero miss count. Releases the GIL for the
    duration, so the prefetch producer thread keeps staging."""
    lib = _require()
    slot_of = np.ascontiguousarray(slot_of, dtype=np.int64)
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    out = np.empty(rows.size, dtype=np.int32)
    bad = lib.ssn_tier_remap(
        _ptr(slot_of), _ptr(rows), rows.size, int(group), _ptr(out))
    return out, int(bad)


def tier_clock_sweep(ref: np.ndarray, pinned: np.ndarray, hand: int,
                     n: int) -> Tuple[np.ndarray, int]:
    """CLOCK victim selection (``TieredTable._allocate`` eviction sweep,
    bit-exact vs the Python loop). Mutates ``ref`` (aging) and ``pinned``
    (selected slots become pinned) IN PLACE; returns ``(victim_slots,
    new_hand)``. ``ref`` must be a writable contiguous uint8 array and
    ``pinned`` a writable contiguous bool/uint8 array of the same length;
    the caller guarantees ``n`` unpinned slots exist."""
    lib = _require()
    assert ref.dtype == np.uint8 and ref.flags.c_contiguous and ref.flags.writeable
    pin8 = pinned.view(np.uint8)
    assert pin8.flags.c_contiguous and pin8.flags.writeable
    assert ref.size == pin8.size
    out = np.empty(max(int(n), 0), dtype=np.int64)
    new_hand = lib.ssn_tier_clock_sweep(
        _ptr(ref), _ptr(pin8), ref.size, int(hand), int(n), _ptr(out))
    return out, int(new_hand)
