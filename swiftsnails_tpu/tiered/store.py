"""Host-RAM master tables with an HBM working-set cache.

The resident store (``parallel/store.py``) caps table size at device memory.
This module adds the missing tier from the reference's design space: the
full-size **master** planes live in host RAM as NumPy arrays (same leaves and
layouts as the device state — dense 2-D ``[C, dim]``, word2vec packed
``[C, S, 128]``, CTR packed-small ``[T, S, 128]``), and the device holds only
a fixed-budget **cache** plane plus a host-side slot map.

The central trick: the cache plane is *just a smaller table of the same
layout*. Every pull/push function and collective derives its capacity and
invalid-row sentinel from ``table.shape[0]``, so once batch ids are remapped
host-side from master units to cache slots, the entire existing data plane —
``pull``/``push``, the packed kernels, the shard_map collectives — runs
verbatim in slot space. Bit-parity with the resident store at f32 follows
because the remap is injective (duplicate-group structure and within-group
order are preserved through ``merge_duplicate_rows``'s stable sort, and XLA
scatter applies duplicate updates in update order, not index order).

Write-back invariant: a cache slot is the unique authoritative copy of its
unit from fault until flush. Dirty slots are flushed device->host on
eviction, on checkpoint (before the manifest is built), and at end of run —
never dropped — so ``master ∪ dirty-cache`` always equals the resident
table's content exactly.

Eviction is frequency-based CLOCK: each slot carries a saturating reference
counter bumped on every hit (and seeded by the vocab-frequency prewarm); the
clock hand halves counters as it sweeps, so hot rows survive many passes and
cold rows age out in O(log ref) sweeps. Slots touched by the current batch
are pinned for the duration of the fault.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

_native_mod = None  # resolved once: the module when usable, False when not


def _native():
    """The native libsnails bindings when the toolchain built them, else
    ``None`` (callers take the NumPy/Python path). Resolved once per process
    — ``available()`` triggers the on-demand g++ build on first use, exactly
    like the data-pipeline call sites."""
    global _native_mod
    if _native_mod is None:
        try:
            from swiftsnails_tpu.data import native

            _native_mod = native if native.available() else False
        except Exception:
            _native_mod = False
    return _native_mod or None


@dataclass
class TierStats:
    """Shared counters for the telemetry surface (goodput block, ledger run
    record). ``lookups``/``hits`` count unique units
    per fault batch; ``faulted_rows``/``evictions`` count cache units (rows
    for the dense/packed layouts, tiles for packed-small).

    The ``*_ns`` fields are the step-time breakdown: host nanoseconds spent
    planning (eager RNG replication, mostly on the prefetch producer thread),
    faulting (``ensure``: residency check + allocation + install dispatch,
    including any flush-queue wait), flushing (synchronous write-back +
    async landings on the flush worker), remapping ids to slot space, and
    dispatching H2D copies of row payloads. Updated from multiple threads
    without locks — a rare lost sample costs telemetry accuracy only."""

    lookups: int = 0
    hits: int = 0
    faults: int = 0  # batched fault events (one per table per faulting step)
    faulted_rows: int = 0  # units moved host -> device
    evictions: int = 0
    flushes: int = 0  # batched write-back events
    flushed_rows: int = 0  # dirty units written device -> host
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    prewarmed_rows: int = 0
    plan_ns: int = 0
    fault_ns: int = 0
    flush_ns: int = 0
    remap_ns: int = 0
    h2d_ns: int = 0
    flush_wait_ns: int = 0  # consumer blocked on the flush queue (drain/full)
    transparent_steps: int = 0  # steps served by the pass-through fast path

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def breakdown(self) -> Dict:
        """The tiered step-time breakdown block (the run record's)."""
        return {
            "plan_ns": self.plan_ns,
            "fault_ns": self.fault_ns,
            "flush_ns": self.flush_ns,
            "remap_ns": self.remap_ns,
            "h2d_ns": self.h2d_ns,
            "flush_wait_ns": self.flush_wait_ns,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
        }

    def as_dict(self) -> Dict:
        return {
            "hit_rate": round(self.hit_rate, 4),
            "lookups": self.lookups,
            "hits": self.hits,
            "faults": self.faults,
            "faulted_rows": self.faulted_rows,
            "evictions": self.evictions,
            "flushes": self.flushes,
            "flushed_rows": self.flushed_rows,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "prewarmed_rows": self.prewarmed_rows,
            "transparent_steps": self.transparent_steps,
            "breakdown": self.breakdown(),
        }


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


_MASK64 = (1 << 64) - 1
_HASH_SEED = 0x5EED5A11  # fixed: digests are process-local, any constant works


def _hash_weights(nbytes: int, seed: int) -> np.ndarray:
    """Fixed pseudo-random odd uint64 weight per byte position — the key of
    the per-unit hash. Odd weights make every byte position full-rank mod
    2^64, so any single flipped bit flips the unit hash."""
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, 1 << 32, size=nbytes, dtype=np.uint64)
    hi = rng.integers(0, 1 << 32, size=nbytes, dtype=np.uint64)
    return (hi << np.uint64(32)) | lo | np.uint64(1)


def _rows_hash(rows: np.ndarray, weights: np.ndarray) -> int:
    """Wraparound-sum keyed hash of a block of units: view each unit's bytes
    as uint8, weight by position, sum everything mod 2^64. Per-unit hashes
    are summed (not chained), so a plane digest updates incrementally —
    subtract the old units' hashes, add the new ones."""
    n = rows.shape[0]
    if n == 0:
        return 0
    flat = np.ascontiguousarray(rows).view(np.uint8).reshape(n, -1)
    return int((flat.astype(np.uint64) * weights).sum(dtype=np.uint64))


MASTER_DTYPES = ("float32", "int8")


def resolve_master_dtype(name: Optional[str]) -> str:
    """Validate / canonicalize a ``tier_master_dtype`` config value."""
    if not name:
        return "float32"
    canon = {"float32": "float32", "f32": "float32",
             "int8": "int8", "s8": "int8"}.get(str(name).strip().lower())
    if canon is None:
        raise ValueError(
            f"tier_master_dtype must be one of {MASTER_DTYPES}, got {name!r}")
    return canon


def _np_hash_uniform(units: np.ndarray, gens: np.ndarray, per: int) -> np.ndarray:
    """Deterministic uniform[0,1) dither [n, per] keyed by (unit id,
    quantization generation, element position) — the NumPy twin of
    ``parallel.comm._hash_uniform``, so master re-quantization is
    reproducible given the scatter history while stays unbiased over
    positions and generations."""
    u = np.asarray(units, np.uint64).astype(np.uint32)
    g = np.asarray(gens, np.uint64).astype(np.uint32)
    seed = (u * np.uint32(2654435761) + g * np.uint32(0x9E3779B9))
    x = np.arange(per, dtype=np.uint32)[None, :] * np.uint32(2654435761)
    x = x + seed[:, None]
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    return x.astype(np.float64) * (1.0 / 4294967296.0)


def _np_quant_unit_rows(rows: np.ndarray, dither: Optional[np.ndarray] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-unit symmetric int8 of ``[n, ...]`` f32 rows -> (codes int8 of
    ``rows.shape``, scales f32 [n] = unit_amax/127; all-zero units get zero
    scale). ``dither`` switches round-to-nearest to unbiased floor(y + u)."""
    n = rows.shape[0]
    flat = np.asarray(rows, np.float32).reshape(n, -1)
    amax = np.abs(flat).max(axis=1) if flat.size else np.zeros(n, np.float32)
    scale = (amax / np.float32(127.0)).astype(np.float32)
    inv = np.divide(np.float32(1.0), scale, where=scale > 0,
                    out=np.zeros_like(scale))
    y = flat * inv[:, None]
    y = np.rint(y) if dither is None else np.floor(y + dither)
    codes = np.clip(y, -127, 127).astype(np.int8).reshape(rows.shape)
    return codes, scale


def _np_dequant_unit_rows(codes: np.ndarray, scales: np.ndarray,
                          dtype) -> np.ndarray:
    """int8 codes + per-unit scales -> rows of the logical dtype."""
    n = codes.shape[0]
    shape = (n,) + (1,) * (codes.ndim - 1)
    return (codes.astype(np.float32)
            * np.asarray(scales, np.float32).reshape(shape)).astype(dtype)


class HostMaster:
    """NumPy master plane for one table: the same (table, slots) leaves as
    the device state, full size, host-resident. ``group`` is the number of
    logical rows per cache unit (1 except the packed-small plane, where one
    unit is a ``[S, 128]`` tile holding G rows).

    ``master_dtype: int8`` stores every float plane as int8 codes plus one
    f32 scale per unit (``amax/127`` over the unit's elements), roughly
    quadrupling the vocab a host holds at fixed RAM. The quantization is
    invisible outside this class: :meth:`gather` dequantizes into the
    logical (f32) dtype the HBM cache uses, :meth:`scatter` re-quantizes
    with a deterministic hash dither keyed by (unit, per-unit quantization
    generation) so repeated flush round trips stay unbiased, and
    :meth:`state` / :meth:`reload` speak full-precision pytrees — the
    on-disk checkpoint format is byte-identical to an f32-master run.
    Integrity digests cover the code planes AND the scale sidebands
    (``<plane>/scale``), both maintained incrementally through scatter."""

    def __init__(self, state, layout: str, group: int = 1,
                 checksums: bool = True, master_dtype: str = "float32"):
        self.kind = type(state)  # TableState | PackedTableState
        self.layout = layout
        self.group = int(group)
        self.master_dtype = resolve_master_dtype(master_dtype)
        # owned, writable copies: device_get hands back views onto read-only
        # buffers, and the masters are mutated in place by every write-back
        table = np.array(jax.device_get(state.table))
        slots = {
            k: np.array(jax.device_get(v)) for k, v in state.slots.items()
        }
        # logical dtypes: what gather/state hand out and what the cache
        # plane is made of — the stored planes may be narrower (int8 codes)
        self.table_dtype = table.dtype
        self.slot_dtypes = {k: v.dtype for k, v in slots.items()}
        self.quantized = self.master_dtype == "int8"
        # per-plane per-unit f32 scale sidebands (quantized masters only),
        # keyed by plane name; per-unit quantization-generation counter
        # salts the scatter-path dither so every re-quantization of a unit
        # draws fresh (but replayable) noise
        self.scales: Dict[str, np.ndarray] = {}
        self._qgen: Optional[np.ndarray] = None
        if self.quantized:
            self._qgen = np.zeros(table.shape[0], np.uint32)
            self.table, self.scales["table"] = _np_quant_unit_rows(table)
            self.slots = {}
            for k, v in slots.items():
                self.slots[k], self.scales[f"slots/{k}"] = (
                    _np_quant_unit_rows(v))
        else:
            self.table = table
            self.slots = slots
        # per-plane integrity digests: a keyed wraparound sum of per-unit
        # hashes, maintained incrementally through scatter() so a direct
        # memory corruption (bit rot, a stray write bypassing scatter) is
        # detectable by verify() at any time
        self._weights: Optional[Dict[str, np.ndarray]] = None
        self._digests: Optional[Dict[str, int]] = None
        if checksums:
            self._init_digests()

    # -- integrity ----------------------------------------------------------

    def _planes(self):
        yield "table", self.table
        for k in sorted(self.slots):
            yield f"slots/{k}", self.slots[k]
        # the scale sidebands are part of the master's content: a flipped
        # scale bit corrupts every element of its unit on dequant, so the
        # digests (and the bitflip chaos drill) must cover them too
        for p in sorted(self.scales):
            yield f"{p}/scale", self.scales[p][:, None]

    def _plane_weights(self, plane: str, arr: np.ndarray) -> np.ndarray:
        per = int(np.prod(arr.shape[1:], dtype=np.int64)) * arr.dtype.itemsize
        w = self._weights.get(plane)
        if w is None or w.shape[0] != per:
            seed = (_HASH_SEED + hash(plane)) & _MASK64
            w = self._weights[plane] = _hash_weights(max(per, 1), seed)
        return w

    def _plane_digest(self, plane: str, arr: np.ndarray,
                      chunk: int = 8192) -> int:
        w = self._plane_weights(plane, arr)
        total = 0
        for start in range(0, arr.shape[0], chunk):
            total = (total + _rows_hash(arr[start:start + chunk], w)) & _MASK64
        return total

    def _init_digests(self) -> None:
        self._weights = {}
        self._digests = {
            plane: self._plane_digest(plane, arr)
            for plane, arr in self._planes()
        }

    @property
    def checksummed(self) -> bool:
        return self._digests is not None

    def _digest_swap(self, plane: str, arr: np.ndarray, units: np.ndarray,
                     old_rows: np.ndarray, new_rows: np.ndarray) -> None:
        w = self._plane_weights(plane, arr)
        d = self._digests[plane]
        d = (d - _rows_hash(old_rows, w)) & _MASK64
        d = (d + _rows_hash(np.asarray(new_rows, dtype=arr.dtype), w)) & _MASK64
        self._digests[plane] = d

    def verify(self) -> list:
        """Recompute every plane digest and compare with the incrementally
        tracked one; returns the names of corrupt planes (``table`` /
        ``slots/<name>``), empty when the masters are intact. Any content
        change that did not flow through :meth:`scatter` — a flipped bit, a
        torn write — shows up here."""
        if self._digests is None:
            return []
        return [
            plane for plane, arr in self._planes()
            if self._plane_digest(plane, arr) != self._digests[plane]
        ]

    def reload(self, state) -> None:
        """Replace the master content wholesale (quarantine-and-rebuild path:
        the caller restored a verified checkpoint) and re-seed the digests.
        Quantized masters re-quantize deterministically (round-to-nearest):
        the heal path must be reproducible, and a reload is a single
        conversion, not a repeated round trip that needs dithering."""
        tab = state["table"] if isinstance(state, dict) else state.table
        slots = state["slots"] if isinstance(state, dict) else state.slots
        table = np.array(jax.device_get(tab))
        slots = {k: np.array(jax.device_get(v)) for k, v in slots.items()}
        if self.quantized:
            self.table, self.scales["table"] = _np_quant_unit_rows(table)
            self.slots = {}
            for k, v in slots.items():
                self.slots[k], self.scales[f"slots/{k}"] = (
                    _np_quant_unit_rows(v))
        else:
            self.table = table
            self.slots = slots
        if self._digests is not None:
            self._init_digests()

    @property
    def units(self) -> int:
        return self.table.shape[0]

    @property
    def unit_nbytes(self) -> int:
        """LOGICAL bytes per unit — the size of the full-precision rows this
        master hands the HBM cache. TierManager sizes the device budget off
        this, so it must not shrink when the host storage narrows."""
        per = int(np.prod(self.table.shape[1:], dtype=np.int64)) or 1
        n = per * self.table_dtype.itemsize
        for k, v in self.slots.items():
            sper = int(np.prod(v.shape[1:], dtype=np.int64)) or 1
            n += sper * self.slot_dtypes[k].itemsize
        return n

    @property
    def host_unit_nbytes(self) -> int:
        """STORED bytes per unit in host RAM (codes + scale sidebands for a
        quantized master) — the capacity-per-GB readout. Equals :attr:`unit_nbytes` for f32 masters."""
        per = int(np.prod(self.table.shape[1:], dtype=np.int64)) or 1
        n = per * self.table.dtype.itemsize
        for v in self.slots.values():
            sper = int(np.prod(v.shape[1:], dtype=np.int64)) or 1
            n += sper * v.dtype.itemsize
        for s in self.scales.values():
            n += s.dtype.itemsize
        return n

    def gather(self, units: np.ndarray) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        if not self.quantized:
            return self.table[units], {k: v[units] for k, v in self.slots.items()}
        t = _np_dequant_unit_rows(self.table[units],
                                  self.scales["table"][units],
                                  self.table_dtype)
        s = {
            k: _np_dequant_unit_rows(v[units], self.scales[f"slots/{k}"][units],
                                     self.slot_dtypes[k])
            for k, v in self.slots.items()
        }
        return t, s

    def scatter(self, units: np.ndarray, table_rows: np.ndarray,
                slot_rows: Dict[str, np.ndarray]) -> None:
        """Write units back into the masters. ``units`` must be unique (every
        call site flushes a slot map, which is injective) — the incremental
        digest update assumes each unit's old bytes are replaced once.

        Quantized masters re-quantize here with a hash dither keyed by
        (unit, generation): unbiased over repeated flush round trips, yet
        deterministic given the scatter history — and order-independent
        across async flush coalescing, because the unique-units contract
        means each unit's generation advances exactly once per landing."""
        units = np.asarray(units)
        if self.quantized and units.size:
            gens = self._qgen[units]
            per = int(np.prod(self.table.shape[1:], dtype=np.int64)) or 1
            codes, scales = _np_quant_unit_rows(
                np.asarray(table_rows, np.float32),
                _np_hash_uniform(units, gens, per))
            new_slot: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
            for k, v in slot_rows.items():
                sper = int(np.prod(self.slots[k].shape[1:],
                                   dtype=np.int64)) or 1
                # salt the generation per plane so planes draw distinct noise
                new_slot[k] = _np_quant_unit_rows(
                    np.asarray(v, np.float32),
                    _np_hash_uniform(units, gens + np.uint32(0x85EBCA6B),
                                     sper))
            if self._digests is not None:
                self._digest_swap("table", self.table, units,
                                  self.table[units], codes)
                self._digest_swap("table/scale", self.scales["table"][:, None],
                                  units, self.scales["table"][units, None],
                                  scales[:, None])
                for k, (c, s) in new_slot.items():
                    self._digest_swap(f"slots/{k}", self.slots[k], units,
                                      self.slots[k][units], c)
                    self._digest_swap(f"slots/{k}/scale",
                                      self.scales[f"slots/{k}"][:, None],
                                      units,
                                      self.scales[f"slots/{k}"][units, None],
                                      s[:, None])
            self.table[units] = codes
            self.scales["table"][units] = scales
            for k, (c, s) in new_slot.items():
                self.slots[k][units] = c
                self.scales[f"slots/{k}"][units] = s
            self._qgen[units] += 1
            return
        if self._digests is not None and units.size:
            self._digest_swap("table", self.table, units,
                              self.table[units], table_rows)
            for k, v in slot_rows.items():
                self._digest_swap(f"slots/{k}", self.slots[k], units,
                                  self.slots[k][units], v)
        self.table[units] = table_rows
        for k, v in slot_rows.items():
            self.slots[k][units] = v

    def state(self):
        """The full-size state pytree (NumPy leaves) — what checkpoints save
        and what the trainer gets back at end of run. Same NamedTuple type,
        shapes, and dtypes as the resident device state, so the on-disk
        checkpoint format is unchanged: quantized masters dequantize BEFORE
        the manifest ever sees a plane (f32 in, f32 out)."""
        if not self.quantized:
            return self.kind(table=self.table, slots=dict(self.slots))
        table = _np_dequant_unit_rows(self.table, self.scales["table"],
                                      self.table_dtype)
        slots = {
            k: _np_dequant_unit_rows(v, self.scales[f"slots/{k}"],
                                     self.slot_dtypes[k])
            for k, v in self.slots.items()
        }
        return self.kind(table=table, slots=slots)


class _FlushQueue:
    """Bounded background write-back drain (``tier_async_flush``).

    The eviction path hands each dirty-victim batch over as already-dispatched
    device gathers (the device snapshot is taken before the slot is reused);
    the worker thread blocks on the D2H ``device_get`` off the step path,
    coalesces up to ``batch`` queued entries per table, and lands them in the
    host masters with one ``scatter`` per table. Correctness rides the
    existing generation protocol: ``master_ver`` bumps only at landing (after
    the master scatter), so a staged install racing an in-flight flush either
    sees the bumped version (flush landed -> mismatch -> discard) or finds the
    unit still pending (the consumer drains before gathering it — see
    ``TieredTable.ensure``). At most one in-flight entry ever holds a given
    unit, because refaulting a pending unit forces that drain first — which is
    what lets the worker concatenate entries and scatter them in one call.

    ``drain()`` is the barrier ``master_state``, checkpoint save, ``heal``,
    ``verify``, and end-of-run use: it returns only when every queued entry
    has landed. Worker errors re-raise at the next ``drain()`` or ``put()``.
    The worker thread starts lazily on the first ``put`` — a run that never
    evicts (or a serving tier, which is read-only) never spawns it.
    """

    def __init__(self, depth: int = 8, batch: int = 8, registry=None):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(int(depth), 1))
        self._batch = max(int(batch), 1)
        self._registry = registry
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._gate = threading.Event()  # test hook: cleared => worker pauses
        self._gate.set()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def qsize(self) -> int:
        return self._q.qsize()

    def put(self, table: "TieredTable", units: np.ndarray, n: int,
            t_dev, s_dev: Dict) -> None:
        """Enqueue one eviction's dirty victims; blocks when the queue is
        full (bounded backpressure — the step path waits rather than letting
        unlanded device snapshots grow without bound)."""
        self._raise_pending()
        if self._thread is None:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._work, daemon=True,
                        name="tier-flush-worker")
                    self._thread.start()
        self._q.put((table, units, n, t_dev, s_dev))

    def drain(self) -> None:
        """Block until every queued entry has landed in its master; re-raise
        any worker error. This is the flush-before-manifest barrier."""
        self._q.join()
        self._raise_pending()

    def _raise_pending(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    # test hooks: freeze/unfreeze the worker to force gather/flush
    # interleavings deterministically
    def pause(self) -> None:
        self._gate.clear()

    def resume(self) -> None:
        self._gate.set()

    def close(self) -> None:
        self._stop.set()
        self._gate.set()

    def _work(self) -> None:
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            entries = [first]
            while len(entries) < self._batch:
                try:
                    entries.append(self._q.get_nowait())
                except queue.Empty:
                    break
            self._gate.wait()
            try:
                self._land(entries)
            except BaseException as e:  # surfaced at the next drain/put
                self._err = e
            finally:
                for _ in entries:
                    self._q.task_done()
            if self._registry is not None:
                self._registry.gauge("tier_flush_queue_depth").set(
                    self._q.qsize())

    def _land(self, entries: List[Tuple]) -> None:
        t0 = time.monotonic_ns()
        by_table: Dict[int, Tuple["TieredTable", List[Tuple]]] = {}
        for table, units, n, t_dev, s_dev in entries:
            by_table.setdefault(id(table), (table, []))[1].append(
                (units, n, t_dev, s_dev))
        for table, chunks in by_table.values():
            table._land_flush(chunks)
        if self._registry is not None:
            self._registry.histogram("tier_flush_ms").observe(
                (time.monotonic_ns() - t0) / 1e6)


class TieredTable:
    """Fixed-budget HBM cache + slot map over one :class:`HostMaster`.

    Holds *no* device arrays: the cache plane flows through the trainer's
    state pytree (so jit donation stays safe), and every method that moves
    data takes the current cache state and returns the updated one.
    """

    def __init__(
        self,
        master: HostMaster,
        budget_units: int,
        *,
        mesh=None,
        name: str = "",
        stats: Optional[TierStats] = None,
        read_only: bool = False,
        flusher: Optional[_FlushQueue] = None,
    ):
        self.master = master
        self.mesh = mesh
        # async write-back: eviction flushes enqueue here instead of blocking
        # the step on the D2H + master scatter; None = synchronous (serving,
        # direct constructions, tier_async_flush: 0)
        self.flusher = flusher
        # units with an enqueued-but-unlanded flush (at most one in-flight
        # entry per unit — refaulting a pending unit drains first). Allocated
        # lazily: a run that never evicts pays nothing.
        self._pending: Optional[np.ndarray] = None
        # rowdma install path state: tri-state eligibility cache plus the
        # reusable pinned host staging buffers, keyed by padded batch size
        self._rowdma: Optional[bool] = None
        self.rowdma_interpret = False  # test hook: run the kernel off-TPU
        self._staging: Dict[int, np.ndarray] = {}
        self.name = name or "table"
        self.stats = stats if stats is not None else TierStats()
        self.read_only = read_only
        # freshness tee: fn(name, units) invoked after every landed master
        # write-back (the dirty-flush stream IS the delta-publish signal);
        # None = no subscriber, zero cost
        self.delta_tap = None
        budget = max(int(budget_units), 1)
        if mesh is not None:
            from swiftsnails_tpu.parallel.mesh import MODEL_AXIS

            model = mesh.shape[MODEL_AXIS]
            budget = -(-budget // model) * model  # rows-per-shard divisibility
        self.budget = min(budget, master.units)
        self.group = master.group
        # host slot map: unit -> cache slot (and inverse), CLOCK state
        self.slot_of = np.full(master.units, -1, np.int64)
        self.unit_of = np.full(self.budget, -1, np.int64)
        self.ref = np.zeros(self.budget, np.uint8)  # saturating frequency
        self.dirty = np.zeros(self.budget, bool)
        self.hand = 0
        self.used = 0  # slots handed out before the clock ever has to evict
        # transparent (pass-through) mode: the budget covers EVERY master
        # unit and the prewarm installed the identity slot map, so no step
        # can ever fault, evict, or need a remap — the per-step plan/ensure
        # bookkeeping is skipped entirely and the tiered run moves at
        # resident speed. Write-back correctness shifts from per-step dirty
        # marking to flush-time "every used slot is dirty" (see flush()).
        self.transparent = False
        # per-unit write-back generation: bumped after every master write, so
        # a staged (prefetched) row whose unit was fault->update->evict-flushed
        # between stage and install is detected as stale and re-gathered —
        # installing it would silently resurrect the pre-update value
        self.master_ver = np.zeros(master.units, np.uint32)

    # -- cache plane construction ------------------------------------------

    def make_cache(self):
        """Zero-filled device cache plane of the master's layout. Unassigned
        slots are never read (pulls only see slots the fault path installed),
        so zeros are safe and skip the RNG init cost."""
        shape = (self.budget,) + self.master.table.shape[1:]
        table = jnp.zeros(shape, self.master.table_dtype)
        slots = {
            k: jnp.zeros((self.budget,) + v.shape[1:],
                         self.master.slot_dtypes[k])
            for k, v in self.master.slots.items()
        }
        if self.mesh is not None:
            from swiftsnails_tpu.parallel.mesh import table_sharding

            sh = table_sharding(self.mesh)
            table = jax.device_put(table, sh)
            slots = {k: jax.device_put(v, sh) for k, v in slots.items()}
        return self.master.kind(table=table, slots=slots)

    # -- id space ----------------------------------------------------------

    def units_for(self, rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows)
        return rows // self.group if self.group > 1 else rows

    def remap(self, rows: np.ndarray) -> np.ndarray:
        """Master row ids -> cache-slot-space row ids (shape/dtype
        preserved). Every unit must be resident (call :meth:`ensure` first).

        Takes the native (GIL-releasing) path for int32 ids when libsnails
        built; the NumPy expression below is the exact reference semantics."""
        rows = np.asarray(rows)
        t0 = time.monotonic_ns()
        nat = _native()
        if nat is not None and rows.dtype == np.int32:
            out, bad = nat.tier_remap(self.slot_of, rows.ravel(), self.group)
            if bad:
                raise RuntimeError(
                    f"tiered[{self.name}]: remap hit a non-resident unit — "
                    "ensure() must cover every id the step touches")
            self.stats.remap_ns += time.monotonic_ns() - t0
            return out.reshape(rows.shape)
        if self.group > 1:
            units = rows // self.group
            slots = self.slot_of[units]
            out = slots * self.group + rows % self.group
        else:
            out = self.slot_of[rows]
        if out.size and int(out.min()) < 0:
            raise RuntimeError(
                f"tiered[{self.name}]: remap hit a non-resident unit — "
                "ensure() must cover every id the step touches")
        self.stats.remap_ns += time.monotonic_ns() - t0
        return out.astype(rows.dtype)

    def peek_missing(self, units: np.ndarray) -> np.ndarray:
        """Sorted unique units not currently resident. Safe to call from the
        staging thread — a stale answer only costs prefetch efficiency."""
        uniq = np.unique(np.asarray(units).ravel())
        return uniq[self.slot_of[uniq] < 0]

    # -- fault path ---------------------------------------------------------

    def ensure(self, cache, units: np.ndarray, *, staged=None,
               mark_dirty: Optional[bool] = None):
        """Make every unit resident; returns the updated cache state.

        ``staged`` is an optional ``(sorted_units, unit_versions,
        device_table_rows, {slot: device_rows})`` payload from the prefetch
        thread — units found there at their staged write-back generation skip
        the host gather + H2D copy on the critical path.
        ``mark_dirty`` defaults to the table's write mode (training marks
        every touched slot dirty — the push *will* write it; serving never
        does).
        """
        t_ensure0 = time.monotonic_ns()
        if mark_dirty is None:
            mark_dirty = not self.read_only
        uniq = np.unique(np.asarray(units).ravel())
        if uniq.size and (int(uniq[0]) < 0 or int(uniq[-1]) >= self.master.units):
            raise ValueError(
                f"tiered[{self.name}]: unit ids out of range "
                f"[{uniq[0]}, {uniq[-1]}] for {self.master.units} units")
        self.stats.lookups += int(uniq.size)
        slots = self.slot_of[uniq]
        resident = slots >= 0
        hit_slots = slots[resident]
        self.stats.hits += int(hit_slots.size)
        self.ref[hit_slots] = np.minimum(
            self.ref[hit_slots].astype(np.int64) + 1, 255
        ).astype(np.uint8)
        miss = uniq[~resident]
        if miss.size:
            if int(hit_slots.size) + int(miss.size) > self.budget:
                raise RuntimeError(
                    f"tiered[{self.name}]: the step touches "
                    f"{int(hit_slots.size) + int(miss.size)} distinct cache "
                    f"units but the HBM budget holds only {self.budget}; "
                    "raise tier_hbm_budget_mb (or shrink the batch)")
            if self._pending is not None and self._pending[miss].any():
                # refault of a unit whose eviction flush is still in flight:
                # the master copy is stale until that entry lands, and the
                # staged version check alone cannot catch a gather taken at
                # the still-current generation — wait the queue out first
                t0 = time.monotonic_ns()
                self.flusher.drain()
                self.stats.flush_wait_ns += time.monotonic_ns() - t0
            new_slots = self._allocate(hit_slots, cache, int(miss.size))
            self.unit_of[new_slots] = miss
            self.slot_of[miss] = new_slots
            self.ref[new_slots] = 1
            self.dirty[new_slots] = False
            self.stats.faults += 1
            self.stats.faulted_rows += int(miss.size)
            cache = self._install(cache, miss, new_slots, staged)
        if mark_dirty and uniq.size:
            self.dirty[self.slot_of[uniq]] = True
        self.stats.fault_ns += time.monotonic_ns() - t_ensure0
        return cache

    def _allocate(self, pinned_slots: np.ndarray, cache, n: int) -> np.ndarray:
        """Grab ``n`` cache slots: unassigned first, then CLOCK eviction
        (dirty victims are flushed to the master before reuse). The sweep
        runs in libsnails when built (it releases the GIL, so the prefetch
        producer keeps moving); the Python loop below is bit-exact."""
        out = np.empty(n, np.int64)
        k = 0
        while k < n and self.used < self.budget:
            out[k] = self.used
            self.used += 1
            k += 1
        if k < n:
            pinned = np.zeros(self.budget, bool)
            pinned[pinned_slots] = True
            pinned[out[:k]] = True
            nat = _native()
            if nat is not None:
                victims, self.hand = nat.tier_clock_sweep(
                    self.ref, pinned, self.hand, n - k)
                out[k:] = victims
                k = n
            while k < n:
                h = self.hand
                self.hand = (self.hand + 1) % self.budget
                if pinned[h]:
                    continue
                if self.ref[h] > 0:
                    self.ref[h] >>= 1  # age; hot slots survive O(log) sweeps
                    continue
                out[k] = h
                pinned[h] = True
                k += 1
            victims = out[self.unit_of[out] >= 0]
            if victims.size:
                self.stats.evictions += int(victims.size)
                vd = victims[self.dirty[victims]]
                if vd.size:
                    self._flush_slots(cache, vd)
                self.slot_of[self.unit_of[victims]] = -1
                self.unit_of[victims] = -1
        return out

    def _install(self, cache, miss: np.ndarray, slots: np.ndarray, staged):
        """Scatter the faulted units' rows into the cache plane — from the
        staged device payload where available, from a host master gather for
        the rest."""
        host_miss, host_slots = miss, slots
        if staged is not None:
            s_units, s_vers, s_table, s_slots = staged
            pos = np.searchsorted(s_units, miss)
            pos_c = np.minimum(pos, max(len(s_units) - 1, 0))
            ok = (
                (len(s_units) > 0)
                & (pos < len(s_units))
                & (s_units[pos_c] == miss)
                # stale staged row: the unit was flushed (fault -> update ->
                # evict) after the stage gathered it — re-gather from master
                & (s_vers[pos_c] == self.master_ver[miss])
            )
            if np.any(ok):
                take = jnp.asarray(pos_c[ok].astype(np.int32))
                idx = slots[ok]
                cache = self._scatter_state(
                    cache, idx, jnp.take(s_table, take, axis=0),
                    {k: jnp.take(v, take, axis=0) for k, v in s_slots.items()},
                )
                host_miss, host_slots = miss[~ok], slots[~ok]
        if host_miss.size:
            t_rows, s_rows = self.master.gather(host_miss)
            self.stats.h2d_bytes += t_rows.nbytes + sum(
                v.nbytes for v in s_rows.values())
            cache = self._scatter_state(cache, host_slots, t_rows, s_rows)
        return cache

    def _rowdma_ok(self) -> bool:
        """Whether faulted host rows install via the Pallas row-scatter
        kernel. Cached after first use — tests setting ``rowdma_interpret``
        must do so before the first fault (or reset ``_rowdma`` to None)."""
        if self._rowdma is None:
            from swiftsnails_tpu.ops import rowdma

            # shapes come from the stored planes (identical either way);
            # dtypes must be the LOGICAL ones — the gathered fault payload a
            # quantized master hands over is already dequantized to f32
            planes = [(self.master.table, self.master.table_dtype)] + [
                (self.master.slots[k], self.master.slot_dtypes[k])
                for k in sorted(self.master.slots)]
            self._rowdma = (
                self.mesh is None
                and (rowdma.on_tpu() or self.rowdma_interpret)
                and all(
                    p.ndim == 3
                    and p.shape[-1] == rowdma.ROW_LANES
                    and dt == self.master.table_dtype
                    for p, dt in planes)
            )
        return self._rowdma

    def _scatter_rowdma(self, cache, idx: np.ndarray, table_rows, slot_rows,
                        n: int, b: int):
        """Install host rows through the double-buffered rowdma scatter from
        ONE fused H2D copy: every plane's rows land in a reusable host
        staging buffer (concatenated along the sublane axis), a single
        ``jnp.asarray`` moves the batch, and each plane is sliced out on
        device. The pow2 pad index == ``budget`` rides the kernel's
        rows >= capacity skip, exactly like the OOB-drop scatter."""
        from swiftsnails_tpu.ops.rowdma import scatter_write_rows

        t0 = time.monotonic_ns()
        keys = sorted(slot_rows)
        spans = [("table", int(self.master.table.shape[1]))] + [
            (k, int(self.master.slots[k].shape[1])) for k in keys]
        total = sum(s for _, s in spans)
        lanes = int(self.master.table.shape[2])
        buf = self._staging.get(b)
        if buf is None or buf.shape != (b, total, lanes):
            buf = self._staging[b] = np.zeros(
                (b, total, lanes), self.master.table_dtype)
        off = 0
        for name, s in spans:
            rows = table_rows if name == "table" else slot_rows[name]
            buf[:n, off:off + s] = rows
            off += s
        idx_p = np.full(b, self.budget, np.int32)
        idx_p[:n] = np.asarray(idx)
        fused = jnp.asarray(buf)  # the one H2D for the whole fault batch
        rows_dev = jnp.asarray(idx_p)
        blk = min(b, 512)  # both pow2, so b % blk == 0
        table = cache.table
        slots = dict(cache.slots)
        off = 0
        for name, s in spans:
            vals = fused[:, off:off + s, :]
            off += s
            if name == "table":
                table = scatter_write_rows(
                    table, rows_dev, vals, block_rows=blk,
                    interpret=self.rowdma_interpret)
            else:
                slots[name] = scatter_write_rows(
                    slots[name], rows_dev, vals, block_rows=blk,
                    interpret=self.rowdma_interpret)
        self.stats.h2d_ns += time.monotonic_ns() - t0
        return self.master.kind(table=table, slots=slots)

    def _scatter_state(self, cache, idx: np.ndarray, table_rows, slot_rows):
        """One bucketed scatter per leaf; pow2 padding (pad index == budget,
        dropped by the OOB-drop scatter) bounds retraces logarithmically."""
        n = int(np.asarray(idx).size)
        b = _pow2(max(n, 1))
        if (
            isinstance(table_rows, np.ndarray)
            and all(isinstance(v, np.ndarray) for v in slot_rows.values())
            and self._rowdma_ok()
        ):
            # host-gathered fault payloads only: staged rows are already on
            # device, so there is no H2D copy left to fuse for them
            return self._scatter_rowdma(
                cache, idx, table_rows, slot_rows, n, b)
        idx_p = np.full(b, self.budget, np.int32)
        idx_p[:n] = np.asarray(idx)

        def pad(vals):
            if b == n:
                return jnp.asarray(vals)
            v = jnp.asarray(vals)
            return jnp.concatenate(
                [v, jnp.zeros((b - n,) + v.shape[1:], v.dtype)])

        if self.mesh is not None:
            from swiftsnails_tpu.parallel.transfer import scatter_slots_collective

            table = scatter_slots_collective(
                self.mesh, cache.table, idx_p, pad(table_rows))
            slots = {
                k: scatter_slots_collective(
                    self.mesh, cache.slots[k], idx_p, pad(slot_rows[k]))
                for k in cache.slots
            }
        else:
            from swiftsnails_tpu.parallel.store import scatter_rows

            table = scatter_rows(cache.table, idx_p, pad(table_rows))
            slots = {
                k: scatter_rows(cache.slots[k], idx_p, pad(slot_rows[k]))
                for k in cache.slots
            }
        return self.master.kind(table=table, slots=slots)

    # -- write-back ----------------------------------------------------------

    def _flush_slots(self, cache, slots: np.ndarray, *,
                     sync: bool = False) -> None:
        """Device -> host write-back of specific cache slots into the master
        (bucketed gather; padding reads slot 0 and is sliced off).

        The device gather is always dispatched here, before the slot can be
        reused — ``gather_rows`` yields fresh output buffers, so the snapshot
        survives the cache plane's later overwrite (or donation) regardless
        of when it is read back. With a flusher attached (and ``sync`` not
        forced), the D2H ``device_get`` + master scatter defer to the
        background worker; otherwise they happen inline."""
        from swiftsnails_tpu.parallel.store import gather_rows

        n = int(slots.size)
        b = _pow2(max(n, 1))
        idx_p = np.zeros(b, np.int32)
        idx_p[:n] = slots
        t_dev = gather_rows(cache.table, idx_p)
        s_dev = {k: gather_rows(v, idx_p) for k, v in cache.slots.items()}
        units = self.unit_of[slots].copy()
        self.dirty[slots] = False
        if self.flusher is not None and not sync:
            if self._pending is None:
                self._pending = np.zeros(self.master.units, np.uint8)
            self._pending[units] = 1
            t0 = time.monotonic_ns()
            self.flusher.put(self, units, n, t_dev, s_dev)
            self.stats.flush_wait_ns += time.monotonic_ns() - t0
            return
        self._land_flush([(units, n, t_dev, s_dev)])

    def _land_flush(self, chunks: List[Tuple]) -> None:
        """Land gathered flush chunks in the master: D2H the device
        snapshots, scatter once per table (chunk units are disjoint — at
        most one in-flight entry per unit — so the concatenation satisfies
        ``scatter``'s unique-units contract), then bump generations and
        clear the pending marks, in that order: a concurrent stage either
        reads the pre-bump version (discarded at install) or sees the
        post-scatter master."""
        t0 = time.monotonic_ns()
        units = np.concatenate([c[0] for c in chunks])
        t_rows = np.concatenate(
            [np.asarray(jax.device_get(c[2]))[:c[1]] for c in chunks])
        s_rows = {
            k: np.concatenate(
                [np.asarray(jax.device_get(c[3][k]))[:c[1]] for c in chunks])
            for k in chunks[0][3]
        }
        self.master.scatter(units, t_rows, s_rows)
        # bump AFTER the scatter: a staging-thread version read that races the
        # write-back sees the old generation and the install discards its row
        self.master_ver[units] += 1
        if self._pending is not None:
            self._pending[units] = 0
        if self.delta_tap is not None:
            try:
                self.delta_tap(self.name, units)
            except Exception:
                pass  # the freshness tee never blocks the write-back
        self.stats.d2h_bytes += t_rows.nbytes + sum(
            v.nbytes for v in s_rows.values())
        self.stats.flushes += 1
        self.stats.flushed_rows += int(units.size)
        self.stats.flush_ns += time.monotonic_ns() - t0

    def drain(self) -> None:
        """Barrier: wait out every queued async flush (no-op when sync)."""
        if self.flusher is not None:
            t0 = time.monotonic_ns()
            self.flusher.drain()
            self.stats.flush_wait_ns += time.monotonic_ns() - t0

    def flush(self, cache) -> None:
        """Write every dirty slot back to the master. After this the master
        holds the exact resident-table content (the write-back invariant);
        the cache stays mapped, so training continues without refaulting.
        Queued async flushes are drained first, then the remaining dirty
        slots go back synchronously — this is a barrier, not an enqueue."""
        self.drain()
        if self.transparent:
            # pass-through mode never marks dirty per step (prepare() skips
            # ensure entirely), and the identity-mapped cache in unit order
            # IS the whole table: replace the master planes wholesale (one
            # D2H per plane, digests re-seeded) instead of a bucketed slot
            # gather + per-unit scatter of everything
            t0 = time.monotonic_ns()
            self.master.reload(cache)
            self.stats.flushes += 1
            self.stats.flushed_rows += self.used
            # what moved D2H is the f32 cache plane, not the (possibly
            # narrower) stored master bytes
            self.stats.d2h_bytes += (
                self.master.units * self.master.unit_nbytes)
            self.stats.flush_ns += time.monotonic_ns() - t0
            return
        d = np.nonzero(self.dirty)[0]
        if d.size:
            self._flush_slots(cache, d, sync=True)

    def writeback_resident(self, cache) -> int:
        """Write EVERY resident slot back to the master, dirty or not — the
        quarantine-and-rebuild path: after the master plane is reloaded from
        an (older) verified checkpoint, the cache is the authoritative copy
        of everything currently resident, so re-asserting it narrows the
        rollback to units that were evicted since that checkpoint. Returns
        the number of units written."""
        self.drain()
        r = np.nonzero(self.unit_of >= 0)[0]
        if r.size:
            self._flush_slots(cache, r, sync=True)
        return int(r.size)

    # -- admission seeding ----------------------------------------------------

    def adopt_resident(self, state):
        """Full-coverage adoption: the budget holds every master unit, so
        the trainer's existing device plane IS the cache — install the
        identity slot map over it and return it unchanged. No zero-fill, no
        master gather, no H2D: the fast twin of ``make_cache`` + a full
        :meth:`prewarm`, and the entry into transparent (pass-through)
        mode."""
        if self.budget < self.master.units:
            raise ValueError(
                f"tiered[{self.name}]: adopt_resident needs the budget "
                f"({self.budget}) to cover every master unit "
                f"({self.master.units})")
        n = self.master.units
        self.slot_of[:] = np.arange(n, dtype=np.int64)
        self.unit_of[:n] = np.arange(n, dtype=np.int64)
        self.used = n
        self.ref[:n] = 3
        self.stats.prewarmed_rows += n
        if not self.read_only:
            self.transparent = True
        return state

    def prewarm(self, cache, units: np.ndarray):
        """Fault the given units (hottest-first) before step 0, clean. Takes
        at most ``budget`` units; seeds their CLOCK counters so the zipf head
        outlives the first eviction sweeps."""
        units = np.asarray(units).ravel()
        if units.size == 0:
            return cache
        # stable unique: keep hottest-first order, drop later duplicates
        _, first = np.unique(units, return_index=True)
        units = units[np.sort(first)][: self.budget]
        cache = self.ensure(cache, units, mark_dirty=False)
        self.ref[self.slot_of[units]] = 3  # survive the first sweeps
        self.stats.prewarmed_rows += int(units.size)
        if (not self.read_only and self.used == self.master.units
                and self.budget == self.master.units
                and np.array_equal(self.unit_of,
                                   np.arange(self.budget, dtype=np.int64))):
            # full coverage with the identity slot map: nothing can ever
            # miss, so the tier degrades to a pass-through (see flush())
            self.transparent = True
        return cache
