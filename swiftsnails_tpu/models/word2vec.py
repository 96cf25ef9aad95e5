"""Word2Vec skip-gram with negative sampling — the flagship trainer.

The reference shipped word2vec as an app over the parameter server
(``src/apps/word2vec``, absent from the snapshot; evidenced by
``src/tools/copy_exec.sh`` ``APP=word2vec``, ``hadoop-server.sh`` shipping
``word2vec.conf`` and ``src/tools/gen-word2vec-data.py``): workers pull
embedding rows for the words in their split, compute SGNS gradients into the
local cache, and push them back to the sharded table (survey §3.3).

TPU-native version: the two embedding tables (input ``syn0`` / output
``syn1neg``) are row-sharded :class:`~swiftsnails_tpu.parallel.store.TableState`
arrays; one jit'd step does pull (gather) -> SGNS loss -> grads w.r.t. the
pulled rows -> push (merge + scatter update). Negative sampling happens
on device via an alias table. This is the BASELINE.json north-star workload
(words/sec/chip).

Config keys: ``dim``, ``window``, ``negatives``, ``learning_rate``,
``num_iters``, ``batch_size``, ``min_count``, ``max_vocab``, ``subsample``,
``hash_keys``, ``capacity``, ``chunk_tokens``, ``seed``, ``data``.
"""

from __future__ import annotations

import contextlib
import functools
import logging
from typing import Dict, Iterator, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from swiftsnails_tpu.data.sampler import (
    AliasTable,
    alias_sample,
    batch_stream,
    build_unigram_alias,
    skipgram_pairs,
    skipgram_windows,
    subsample_mask,
)
from swiftsnails_tpu.data.text import encode_corpus
from swiftsnails_tpu.data.vocab import Vocab
from swiftsnails_tpu.ops.hashing import hash_row
from swiftsnails_tpu.ops.rowdma import unpack_rows
from swiftsnails_tpu.parallel.access import SgdAccess
from swiftsnails_tpu.parallel.store import (
    PackedTableState,
    TableState,
    create_packed_table,
    create_table,
    pull,
    pull_packed,
    push,
    push_packed,
)
from swiftsnails_tpu.framework.trainer import Trainer
from swiftsnails_tpu.utils.config import Config
from swiftsnails_tpu.utils.profiling import phase_scope


class W2VState(NamedTuple):
    in_table: TableState  # syn0: center-word embeddings
    out_table: TableState  # syn1neg: context/negative embeddings


def sgns_loss(v: jax.Array, u_pos: jax.Array, u_neg: jax.Array) -> jax.Array:
    """Skip-gram negative-sampling loss.

    ``v``: [B, D] center rows; ``u_pos``: [B, D] context rows;
    ``u_neg``: [B, K, D] negative rows. Mean over batch of
    ``-log σ(v·u_pos) - Σ_k log σ(-v·u_neg_k)``.

    With bf16 tables the dot products accumulate in f32
    (``preferred_element_type``) and all loss math past the logits is f32, so
    only the row storage/bandwidth is reduced precision.
    """
    pos = jnp.einsum("bd,bd->b", v, u_pos, preferred_element_type=jnp.float32)
    neg = jnp.einsum("bd,bkd->bk", v, u_neg, preferred_element_type=jnp.float32)
    return -(jax.nn.log_sigmoid(pos) + jax.nn.log_sigmoid(-neg).sum(axis=-1)).mean()


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


from swiftsnails_tpu.models.registry import register_model


@register_model("word2vec")
class Word2VecTrainer(Trainer):
    name = "word2vec"

    def __init__(
        self,
        config: Config,
        mesh=None,
        corpus_ids: Optional[np.ndarray] = None,
        vocab: Optional[Vocab] = None,
        tracer=None,
    ):
        super().__init__(config, mesh, tracer)
        cfg = config
        self.dim = cfg.get_int("dim", 100)
        self.window = cfg.get_int("window", 5)
        self.negatives = cfg.get_int("negatives", 5)
        self.lr = cfg.get_float("learning_rate", 0.025)
        # word2vec.c convention: alpha decays linearly over the training run
        # (words consumed / total words), floored at 1e-4 x the start rate.
        # Off by default — the reference PS app surface (SwiftWorker.h:78-83)
        # exposes a constant learning_rate; decay is the word2vec.c refinement.
        self.lr_decay = cfg.get_bool("lr_decay", False)
        self.epochs = cfg.get_int("num_iters", 1)
        self.batch_size = cfg.get_int("batch_size", 1024)
        self.subsample = cfg.get_float("subsample", 1e-4)
        self.hash_keys = cfg.get_bool("hash_keys", False)
        self.chunk_tokens = cfg.get_int("chunk_tokens", 1 << 20)
        self.seed = cfg.get_int("seed", 0)
        self.table_dtype = {
            "float32": jnp.float32, "bfloat16": jnp.bfloat16,
        }[cfg.get_str("table_dtype", "float32")]
        # Fast path: packed [C, S, 128] tables + row-DMA kernels; with a
        # mesh the same kernels run shard-local inside the shard_map
        # collectives (transfer.pull/push_collective_packed). See ops/rowdma.
        self.packed = cfg.get_bool("packed", True)
        # Negative sampling mode: "pool" shares a pool of `pool_size`
        # negatives across each `pool_block` consecutive pairs, scored on the
        # MXU and down-weighted by negatives/pool_size — same expected SGNS
        # gradient, a fraction of the row traffic. "per_pair" is the
        # reference-faithful independent-K sampling ("pool" needs packed
        # tables; the dense path always trains per-pair).
        self.neg_mode = cfg.get_str("neg_mode", "pool" if self.packed else "per_pair")
        if self.neg_mode == "pool" and not self.packed:
            raise ValueError("neg_mode: pool requires packed tables (packed: 1)")
        self.pool_size = cfg.get_int("pool_size", 64)
        self.pool_block = cfg.get_int("pool_block", 512)
        # fused: 1 -> single device: the single-kernel hogwild substep
        # (ops/fused_sgns.py; reference async-SGD semantics). Under a mesh
        # the grouped schema runs the collective grouped plane instead
        # (_substep_grouped_mesh): same center-major traffic cut, shard-local
        # row-DMA kernels inside the shard_map pull/push collectives.
        # Requires packed+pool.
        self.fused = (
            cfg.get_bool("fused", False)
            and self.packed
            and self.neg_mode == "pool"
        )
        # grouped: 1 -> center-major fused kernel (word2vec.c loop order: one
        # center-row DMA per window instead of per pair; the per-row copy
        # issue rate is the fused kernel's measured bound). Batches switch to
        # the {"centers" [N], "contexts" [N, 2*window]} window schema.
        self.grouped = cfg.get_bool("grouped", False) and self.fused
        if cfg.get_bool("grouped", False) and not cfg.get_bool("fused", False):
            raise ValueError("grouped: 1 requires fused: 1")
        # resident: 1 -> grouped kernel + VMEM-resident head rows: rows
        # < hot_rows of both tables live on-chip for the whole substep, read
        # via one-hot MXU expansion and updated with exact merged gradient
        # sums (deterministic for hot rows; see ops/fused_sgns.py). Wins when
        # row ids are frequency-ranked (Vocab order) so the zipf head stays
        # resident; with hash_keys the hot set is arbitrary (correct, less
        # win).
        self.resident = cfg.get_bool("resident", False) and self.grouped
        if cfg.get_bool("resident", False) and not cfg.get_bool("grouped", False):
            raise ValueError("resident: 1 requires grouped: 1")
        self.hot_rows = cfg.get_int("hot_rows", 1024)
        # dedup: 1 -> per-block context-read dedup (fused_sgns_dedup_step)
        # over BLOCK-ORDERED batches: one DMA per distinct context row per
        # block instead of per slot. Requires grouped: 1. COMPOSES with
        # resident: 1 (fused_sgns_dedup_resident_step): the zipf head lives
        # VMEM-resident while cold context rows keep the dedup treatment —
        # requires u_cap >= effective hot_rows (the kernel enforces it).
        self.dedup = cfg.get_bool("dedup", False) and self.grouped
        if cfg.get_bool("dedup", False) and not cfg.get_bool("grouped", False):
            raise ValueError("dedup: 1 requires grouped: 1")
        self.u_cap = cfg.get_int("u_cap", 512)
        # centers per kernel block; per-substep center count is batch_size
        self.centers_per_block = cfg.get_int("centers_per_block", 256)
        # lr reaches the fused kernels as a scalar-prefetch operand (SMEM),
        # so lr_decay works on every path without recompiling per lr value
        # scan this many optimizer substeps per dispatch (amortizes host->TPU
        # dispatch latency). NOTE: TrainLoop steps/checkpoints count
        # dispatches, so substeps scale throughput, not the step counter.
        self.steps_per_call = max(cfg.get_int("steps_per_call", 1), 1)
        # push_mode: "gather" = exact all_gather-over-data push (default);
        # "bucketed" = owner-bucketed push (transfer.push_collective_packed_
        # bucketed): ~model/slack less ICI traffic, MoE-style static bucket
        # capacity — distinct owned rows beyond cap are dropped for the step
        # and reported in the `push_dropped` metric.
        self.push_mode = cfg.get_str("push_mode", "gather")
        if self.push_mode not in ("gather", "bucketed"):
            raise ValueError(f"push_mode must be gather|bucketed, got {self.push_mode}")
        if self.push_mode == "bucketed" and (
            not self.packed or (self.fused and mesh is None)
        ):
            # only the packed collective path routes through _ppush; dense
            # uses the pjit store.push and single-device fused bypasses push
            # entirely — accepting the key there would silently run the
            # exact push while reporting push_dropped: 0. Under a mesh the
            # fused-grouped plane pushes through _ppush, so bucketed works.
            raise ValueError(
                "push_mode: bucketed requires packed: 1, and fused: 1 only "
                "with a mesh (single-device fused has no push collective)")
        self.bucket_slack = cfg.get_float("bucket_slack", 2.0)
        # comm_dtype: ICI payload compression for every mesh collective —
        # f32 (default, bit-identical HLO), bf16 (~2x fewer payload bytes),
        # int8 (per-row scale, stochastic-rounded gradients, ~3.5x), int4
        # (block-wise nibble codes + bf16 block scales, ~7x;
        # comm_int4_block overrides the 32-lane default). The master tables
        # and all shard-local math stay full precision; only the
        # all_gather/psum wire format narrows (parallel/comm.py,
        # docs/SCALING.md). Meaningless without a mesh (no collectives).
        from swiftsnails_tpu.parallel.comm import (apply_int4_block,
                                                   resolve_comm_dtype)

        self.comm_dtype = apply_int4_block(
            resolve_comm_dtype(cfg.get_str("comm_dtype", "float32")),
            cfg.get_int("comm_int4_block", 0))
        # optimizer_sharding: zero (parallel/zero.py): word2vec trains SGD
        # (no slot planes), so zero here is a wire-path change — the hybrid
        # head push reduce-scatters the summed grad, updates the owned
        # slice, and all-gathers params back (bit-identical at f32)
        self.zero = (self.optimizer_sharding == "zero"
                     and self.mesh is not None)
        # overlap: 1|2 -> software-pipelined macro-step on the grouped mesh
        # plane. Depth 1: substep i's push collectives issue together with
        # substep i+1's pull (which reads the PRE-push tables — stale-by-one
        # reads, the reference's async-SGD semantics), so XLA can emit async
        # -start/-done collective pairs that run under compute. Depth 2: a
        # true double-buffered pipeline — TWO pulls stay in flight, so the
        # push+update of substep i overlaps a FULL substep of compute (pulls
        # read stale-by-two state; same async-SGD family, one step deeper).
        # Takes effect only under a mesh with steps_per_call > depth;
        # single-device grouped runs the fused kernel unchanged.
        try:
            self.overlap = cfg.get_int("overlap", 0)
        except ValueError:  # bool spellings (overlap: true) keep working
            self.overlap = int(cfg.get_bool("overlap", False))
        if self.overlap not in (0, 1, 2):
            raise ValueError(
                f"overlap must be 0, 1 or 2, got {self.overlap}")
        if self.overlap and not (
            cfg.get_bool("fused", False) and cfg.get_bool("grouped", False)
        ):
            raise ValueError(
                "overlap: 1|2 requires fused: 1, grouped: 1 (the grouped "
                "collective plane is the only overlap-scheduled path)")

        # table_tier: host -> the tiered parameter store (tiered/): host-RAM
        # master tables, HBM working-set cache, batch ids remapped to cache
        # slots before dispatch. Supported on the dense and packed
        # (pool/per_pair) substeps — the fused/grouped kernels address whole
        # tables in VMEM and have no slot-space meaning. Negative sampling
        # moves host-side (tier_plan replicates the in-jit RNG derivation
        # bit-exactly), so the fault path knows every row before the step.
        self.tiered = cfg.get_str("table_tier", "device") == "host"
        if self.tiered and self.fused:
            raise ValueError(
                "table_tier: host does not compose with fused/grouped "
                "kernels (they take whole-table VMEM references); use "
                "packed: 1 with neg_mode pool/per_pair, or packed: 0")
        # stream: 1 = bounded-memory ingestion — the corpus is never
        # materialized; batches() re-opens a chunk stream each epoch
        # (scan_file_by_line parity; required for corpora larger than RAM).
        self.stream = cfg.get_bool("stream", False)
        self._chunk_factory = None
        self._local_total = None  # approx local tokens/epoch (progress denom)
        if corpus_ids is None:
            with self.span("load-data"):  # vocabulary scan + corpus encode
                data_path = cfg.get_str("data")
                if self.stream:
                    from swiftsnails_tpu.data.text import encode_corpus_stream
                    from swiftsnails_tpu.parallel.cluster import byte_span, process_info

                    span = (0, 0)
                    n_proc = 1
                    if cfg.get_bool("shard_data", True):
                        span = byte_span(data_path)
                        n_proc = process_info()[1]
                    vocab, self._chunk_factory = encode_corpus_stream(
                        data_path,
                        self.chunk_tokens,
                        min_count=cfg.get_int("min_count", 5),
                        max_vocab=cfg.get_int("max_vocab", 0) or None,
                        byte_start=span[0],
                        byte_end=span[1],
                    )
                    # even byte spans => ~even token spans (progress denominator)
                    self._local_total = max(int(vocab.counts.sum()) // n_proc, 1)
                else:
                    corpus_ids, vocab = encode_corpus(
                        data_path,
                        min_count=cfg.get_int("min_count", 5),
                        max_vocab=cfg.get_int("max_vocab", 0) or None,
                    )
                    # Multi-host: train on this process's contiguous corpus span
                    # (stdin-split parity; vocab stays global so ids/placement
                    # agree across hosts). shard_data: 0 = every host trains all.
                    if cfg.get_bool("shard_data", True):
                        from swiftsnails_tpu.parallel.cluster import shard_token_stream

                        corpus_ids = shard_token_stream(corpus_ids)
        assert vocab is not None, "vocab required when corpus_ids is given"
        if corpus_ids is not None:
            self.corpus_ids = np.asarray(corpus_ids, dtype=np.int32)
            self._local_total = len(self.corpus_ids)
        else:
            self.corpus_ids = None
        self.vocab = vocab
        cap = cfg.get_int("capacity", 0) or _next_pow2(max(len(vocab), 2))
        self.capacity = cap
        if not self.hash_keys and len(vocab) > cap:
            raise ValueError(
                f"vocab {len(vocab)} exceeds capacity {cap}; set hash_keys: 1"
            )
        self.access = SgdAccess()
        with self.span("alias-table"):
            self.neg_alias = build_unigram_alias(vocab.counts)
        # placement: uniform|hybrid|auto — hybrid head/tail split of both
        # tables: the zipf head replicated (dense grad reduce over `data`),
        # the tail model-sharded through the collective twins in tail slot
        # space (parallel/hybrid.py). `auto` picks the cut from the vocab
        # frequency CDF + the calibrated wire-cost model
        # (parallel/placement.py); see docs/SCALING.md.
        self._init_placement(cfg)
        self._plan_fns = {}  # (substeps, neg shape) -> jitted tier planner
        if self.resident:
            # surface the kernel's rounding so operators see what actually
            # runs: hot_rows clips to capacity and rounds to the one-hot
            # chunk size; < 8 rows falls back to the grouped kernel entirely
            from swiftsnails_tpu.ops.fused_sgns import effective_hot_rows

            eff, _ = effective_hot_rows(self.hot_rows, self.capacity)
            log = logging.getLogger(__name__)
            if eff < 8:
                log.warning(
                    "resident: 1 with hot_rows=%d (capacity %d) leaves <8 "
                    "resident rows; falling back to the grouped kernel",
                    self.hot_rows, self.capacity,
                )
            elif eff != self.hot_rows:
                log.info(
                    "resident hot_rows=%d rounds to %d effective resident "
                    "rows (capacity clip + one-hot chunk size)",
                    self.hot_rows, eff,
                )

    # -- state -------------------------------------------------------------

    def init_state(self) -> W2VState:
        make = create_packed_table if self.packed else create_table
        in_table = make(
            self.capacity, self.dim, self.access, mesh=self.mesh, seed=self.seed,
            dtype=self.table_dtype,
        )
        # reference word2vec inits syn1neg to zeros; init_scale=0 keeps that
        out_table = make(
            self.capacity, self.dim, self.access, mesh=self.mesh,
            seed=self.seed + 1, init_scale=0.0, dtype=self.table_dtype,
        )
        return W2VState(in_table=in_table, out_table=out_table)

    def _rows(self, keys: jax.Array) -> jax.Array:
        if self.hash_keys:
            return hash_row(keys, self.capacity)
        return keys

    def _step_rows(self, keys: jax.Array) -> jax.Array:
        """In-substep id resolution. On the host tier the batch arrives
        already hashed AND remapped to cache slots (tier_plan/TieredTable),
        so the in-jit hash must not run again; export/eval paths keep
        :meth:`_rows` against the full master table."""
        if self.tiered:
            return keys
        return self._rows(keys)

    # -- placement (hybrid head/tail split; parallel/hybrid.py) --------------

    def _init_placement(self, cfg) -> None:
        from swiftsnails_tpu.parallel.placement import resolve_placement

        requested = resolve_placement(cfg.get_str("placement", "uniform"))
        self.placement = requested
        self.placement_head_rows = cfg.get_int("placement_head_rows", 0)
        self.placement_slack = cfg.get_float("placement_tail_slack", 2.0)
        self.placement_cut = 0
        self.placement_cov = 0.0
        self.placement_decision = None
        if requested == "uniform":
            return
        log = logging.getLogger(__name__)

        def resolve_uniform(reason: str) -> None:
            log.warning("placement: %s requested but %s; running uniform",
                        requested, reason)
            self.placement = "uniform"
            self.placement_decision = {
                "mode": "uniform", "requested": requested, "cut": 0,
                "replicated_rows": 0, "reason": reason,
            }

        if self.mesh is None:
            # nothing to replicate against — and no collectives to save
            return resolve_uniform("no mesh")
        if self.tiered:
            # both remap row ids host-side; composing the two remaps is out
            # of scope — the tiered store already keeps the head HBM-resident
            return resolve_uniform(
                "table_tier: host already caches the hot head")
        from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

        model = self.mesh.shape[MODEL_AXIS]
        data = self.mesh.shape[DATA_AXIS]
        calib = cfg.get_float("placement_calib_bytes", 0.0)
        decision = {"requested": requested,
                    "measured_uniform_bytes": calib or None}
        if requested == "auto":
            if self.hash_keys:
                # hashed ids are not frequency ranks: a prefix cut is an
                # arbitrary row set, so the CDF-driven cut has no meaning
                return resolve_uniform(
                    "hash_keys scrambles frequency ranks (explicit "
                    "placement: hybrid still works)")
            from swiftsnails_tpu.parallel.placement import choose_cut

            n = self.batch_size
            if self.packed:
                pc = self._effective_pc(n)
                local_slots = max(
                    (n * 2 * self.window + (n // pc) * self.pool_size) // data,
                    1)
                row_elems = -(-self.dim // 128) * 128
            else:
                local_slots = max(n * (1 + self.negatives) // data, 1)
                row_elems = self.dim
            decision.update(choose_cut(
                self.vocab.counts, self.capacity, align=model,
                local_slots=local_slots, row_elems=row_elems, data=data,
                slack=self.placement_slack, comm_dtype=self.comm_dtype,
                measured_uniform_bytes=calib or None,
            ))
            cut = decision["cut"]
        else:
            cut = self.placement_head_rows or min(1024, self.capacity // 2)
        cut = min(int(cut), self.capacity // 2)
        align = model
        if self.zero:
            # the ZeRO head push updates a 1/data row slice per replica, so
            # the cut must divide by the data axis too
            import math

            align = math.lcm(model, data)
        cut -= cut % align
        if cut <= 0:
            resolve_uniform("cut resolved to 0 (flat distribution or "
                            "head smaller than the model axis)")
            self.placement_decision.update(
                {k: v for k, v in decision.items() if k != "requested"})
            return
        self.placement_cut = cut
        self.placement_cov = (
            0.0 if self.hash_keys else self.vocab.coverage_at(cut))
        decision.update({
            "mode": "hybrid", "cut": cut,
            "replicated_rows": 2 * cut,  # both tables split at the same cut
            "coverage": self.placement_cov,
        })
        self.placement_decision = decision
        log.info("placement: hybrid cut=%d (coverage %.3f, requested %s)",
                 cut, self.placement_cov, requested)

    def placement_spec(self):
        """Per-table split spec for PlacementManager (None = uniform)."""
        if not self.placement_cut:
            return None
        return {
            "in_table": {"cut": self.placement_cut, "group": 1},
            "out_table": {"cut": self.placement_cut, "group": 1},
        }

    def _hybrid_cap(self, n_rows: int) -> int:
        """Static unique capacity for a hybrid tail pull/push over
        ``n_rows`` global rows: the head's coverage says how few distinct
        tail rows a batch can touch, so the dedup payload shrinks to
        ``slack * (1 - coverage)`` of the local slot count — the structural
        wire-byte cut of the hybrid layout."""
        override = self.config.get_int("placement_tail_cap", 0)
        if override:
            return override
        from swiftsnails_tpu.parallel.mesh import DATA_AXIS
        from swiftsnails_tpu.parallel.placement import tail_cap

        d = self.mesh.shape[DATA_AXIS]
        return tail_cap(max(n_rows // d, 1), self.placement_cov,
                        self.placement_slack)

    def _tbl_scope(self, tbl):
        return (jax.named_scope(f"ssn_tbl_{tbl}") if tbl
                else contextlib.nullcontext())

    def _mesh_safe_cat(self, parts):
        """Leading-axis concatenate that survives GSPMD on a (data, model)
        mesh. GSPMD on this jax/XLA line assembles a ``concatenate`` of
        mixed-lineage operands (data-sharded batch lineage vs replicated
        rng/sample lineage) by dynamic-update-slicing each device's piece
        into a zero buffer and ALL-REDUCE-SUMMING across the WHOLE mesh —
        the compiled HLO shows ``all-reduce(replica_groups={all devices},
        op_name=.../concatenate)``. Along ``model`` the devices hold
        identical copies, not disjoint slices, so every element arrives
        multiplied by the model-axis size: silent garbage row ids / scaled
        gradients (the grouped-mesh shape-invariance breaker). Sharding
        constraints and optimization barriers on the operands or result do
        not stop it — the sum IS the lowering of the concat. Expressing the
        same value as pad-to-length + elementwise add never invokes the
        concat partitioner, and elementwise ops partition soundly.
        (Observed on jax 0.4.x. Kept under jax 0.9.0: with it the 2x2 mesh
        on four chips matches the 1x1 mesh bit for bit — chip_smoke.py leg
        C — and it has not been re-tested WITHOUT it on the chip.)"""
        if self.mesh is None or len(parts) == 1:
            return jnp.concatenate(parts)
        total = sum(p.shape[0] for p in parts)
        tail = ((0, 0),) * (parts[0].ndim - 1)
        out, off = None, 0
        for p in parts:
            padded = jnp.pad(p, ((off, total - off - p.shape[0]),) + tail)
            out = padded if out is None else out + padded
            off += p.shape[0]
        return out

    def _shard_substeps(self, c_t, x_t):
        """Lay the ``[t, b, ...]`` substep stack out with ``b`` split over
        ``data``, as every substep's ``shard_map`` will want it. The macro
        batch arrives split over ``data`` along its one leading axis; left
        to pick a layout for the reshaped stack itself, the partitioner
        produces a scanned program the XLA TPU compiler rejects on a 2x2
        mesh (``Reshape should have supported layout before reaching the
        emitter``, jax 0.9.0 / libtpu 0.0.34; ``steps_per_call: 1`` and a
        1x1 mesh compile). Which rows a substep trains on does not change:
        the first-macro loss on four chips equals the 1x1 mesh's bit for
        bit (chip_smoke.py leg C)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from swiftsnails_tpu.parallel.mesh import DATA_AXIS

        def split_b(x):
            spec = P(None, DATA_AXIS, *([None] * (x.ndim - 2)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))

        return split_b(c_t), split_b(x_t)

    def _id_cat(self, *parts):
        """Concatenate row-id vectors (mesh-safe, see _mesh_safe_cat)."""
        return self._mesh_safe_cat(list(parts))

    # packed pull/push dispatch: single-device kernels, or shard_map
    # collectives wrapping the same kernels when a mesh is present; hybrid
    # table states route through the head/tail twins (parallel/hybrid.py)
    def _ppull(self, table_state, rows, tbl=None):
        if self.mesh is None:
            return pull_packed(table_state, rows)
        from swiftsnails_tpu.parallel.hybrid import is_hybrid

        with self._tbl_scope(tbl):
            if is_hybrid(table_state):
                from swiftsnails_tpu.parallel.hybrid import pull_hybrid_packed

                # index/overflow are discarded: the matching push recomputes
                # the same deterministic unique list and counts the overflow
                # once there
                vals, _, _ = pull_hybrid_packed(
                    self.mesh, table_state, rows,
                    self._hybrid_cap(rows.shape[0]),
                    comm_dtype=self.comm_dtype)
                return vals
            from swiftsnails_tpu.parallel.transfer import pull_collective_packed

            return pull_collective_packed(
                self.mesh, table_state, rows, comm_dtype=self.comm_dtype)

    def _comm_seed(self, rng):
        """uint32 dither seed for int8/int4 stochastic rounding (None unless
        an integer wire format is active — keeps every other path op-free)."""
        from swiftsnails_tpu.parallel.comm import seed_from_key, stochastic_wire

        if not stochastic_wire(self.comm_dtype) or self.mesh is None:
            return None
        return seed_from_key(rng)

    def _ppush(self, table_state, rows, grads, lr, seed=None, tbl=None):
        """Returns ``(new_table_state, dropped)`` — dropped is always 0 except
        in bucketed push mode (static bucket overflow, see transfer.py) and
        hybrid placement (tail unique-capacity overflow, hybrid.py)."""
        if self.mesh is None:
            return push_packed(table_state, rows, grads, self.access, lr), jnp.int32(0)
        from swiftsnails_tpu.parallel.hybrid import is_hybrid

        with self._tbl_scope(tbl):
            if is_hybrid(table_state):
                from swiftsnails_tpu.parallel.hybrid import (
                    push_hybrid_packed,
                    push_hybrid_packed_bucketed,
                )

                if self.push_mode == "bucketed":
                    return push_hybrid_packed_bucketed(
                        self.mesh, table_state, rows, grads, self.access, lr,
                        slack=self.bucket_slack, comm_dtype=self.comm_dtype,
                        seed=seed, zero=self.zero)
                return push_hybrid_packed(
                    self.mesh, table_state, rows, grads, self.access, lr,
                    self._hybrid_cap(rows.shape[0]),
                    comm_dtype=self.comm_dtype, seed=seed, zero=self.zero)
            if self.push_mode == "bucketed":
                from swiftsnails_tpu.parallel.transfer import (
                    push_collective_packed_bucketed,
                )

                return push_collective_packed_bucketed(
                    self.mesh, table_state, rows, grads, self.access, lr,
                    slack=self.bucket_slack, comm_dtype=self.comm_dtype,
                    seed=seed,
                )
            from swiftsnails_tpu.parallel.transfer import push_collective_packed

            return push_collective_packed(
                self.mesh, table_state, rows, grads, self.access, lr,
                comm_dtype=self.comm_dtype, seed=seed,
            ), jnp.int32(0)

    # -- data --------------------------------------------------------------

    def _epoch_chunks(self) -> Iterator[np.ndarray]:
        """Token chunks for one epoch: corpus slices, or the bounded-memory
        stream (re-opened per epoch) in ``stream: 1`` mode."""
        if self.corpus_ids is not None:
            ids = self.corpus_ids
            for start in range(0, len(ids), self.chunk_tokens):
                yield ids[start : start + self.chunk_tokens]
        else:
            yield from self._chunk_factory()

    def batches(self) -> Iterator[Dict[str, np.ndarray]]:
        from swiftsnails_tpu.data import native

        use_native = native.use_native(self.config)
        rng = np.random.default_rng(self.seed)
        counts = self.vocab.counts
        # progress = fraction of this process's corpus consumed (raw tokens x
        # epochs, the word2vec.c word_count convention) — drives linear lr
        # decay. In stream mode the denominator is the byte-span-estimated
        # local token count (exact for the non-streaming path).
        local_total = max(self._local_total or 1, 1)
        total_tokens = max(self.epochs * local_total, 1)
        for epoch in range(self.epochs):
            consumed = 0  # local tokens before this chunk
            for chunk in self._epoch_chunks():
                seed = (self.seed * 1_000_003 + epoch * 7919 + consumed) & 0xFFFFFFFF
                chunk_base = epoch * local_total + consumed
                chunk_len = len(chunk)
                consumed += chunk_len
                if use_native:
                    if self.subsample > 0:
                        chunk = native.subsample(chunk, counts, self.subsample, seed=seed)
                elif self.subsample > 0:
                    chunk = chunk[subsample_mask(chunk, counts, self.subsample, rng)]
                if self.grouped:
                    # center-major window schema for the grouped kernel; one
                    # batch row = one corpus position (word), whole windows
                    # shuffle together (word2vec.c pair order within). The
                    # dedup kernel shuffles at BLOCK granularity instead, so
                    # each kernel block keeps corpus-local (overlapping)
                    # windows — the locality its unique-row copy list needs.
                    from swiftsnails_tpu.data.sampler import batch_stream_blocks

                    if use_native:
                        g_c, g_x = native.skipgram_windows(
                            chunk, self.window, seed=seed
                        )
                    else:
                        g_c, g_x = skipgram_windows(chunk, self.window, rng)
                    macro = self.batch_size * self.steps_per_call
                    n_batches = max(len(g_c) // macro, 1)
                    # Block-order only where a kernel consumes it: the mesh
                    # plane dedups at SUBSTEP granularity (shard-local unique
                    # lists, transfer.py), so block shuffling there would
                    # trade SGD mixing for nothing. The sampler block
                    # must equal the kernel's EFFECTIVE centers_per_block
                    # (largest divisor of the per-substep batch — the same
                    # shrink _substep_grouped applies), so kernel blocks never
                    # straddle shuffled sampler blocks; batch_size divides the
                    # macro batch, so the divisor chain holds end to end.
                    block = (
                        self._effective_pc()
                        if self.dedup and self.mesh is None
                        else 1
                    )
                    if use_native and len(g_c) >= macro:
                        # native assembly: C++ worker threads gather batches
                        # behind a bounded ticket ring (block mode copies
                        # whole contiguous window spans)
                        stream = native.WindowPrefetcher(
                            g_c, g_x, macro, block=block, epochs=1,
                            capacity=4, seed=seed,
                        )
                    elif block > 1:
                        stream = batch_stream_blocks(g_c, g_x, macro, rng,
                                                     block=block)
                    else:
                        stream = batch_stream(g_c, g_x, macro, rng)
                    try:
                        for bi, b in enumerate(stream):
                            p = (chunk_base + (bi / n_batches) * chunk_len) / total_tokens
                            yield {**b, "progress": np.float32(min(p, 1.0))}
                    finally:
                        if hasattr(stream, "close"):
                            stream.close()
                    continue
                if use_native:
                    centers, contexts = native.skipgram_pairs(
                        chunk, self.window, seed=seed
                    )
                else:
                    centers, contexts = skipgram_pairs(chunk, self.window, rng)
                # macro-batches: steps_per_call optimizer steps per dispatch.
                # Native path: the C++ PairPrefetcher shuffles and slices in
                # a producer thread behind a bounded queue
                # (queue_with_capacity parity, src/utils/queue.h:100-108), so
                # batch assembly overlaps device compute instead of running
                # on the dispatch thread.
                macro = self.batch_size * self.steps_per_call
                n_batches = max(len(centers) // macro, 1)
                if use_native and len(centers) >= macro:
                    stream = native.PairPrefetcher(
                        centers, contexts, macro, epochs=1, capacity=4,
                        seed=seed,
                    )
                else:
                    stream = batch_stream(centers, contexts, macro, rng)
                try:
                    for bi, b in enumerate(stream):
                        p = (chunk_base + (bi / n_batches) * chunk_len) / total_tokens
                        yield {**b, "progress": np.float32(min(p, 1.0))}
                finally:
                    if hasattr(stream, "close"):
                        stream.close()

    # -- step --------------------------------------------------------------

    def _mesh_u_cap(self, n: int) -> int:
        """Static unique-list capacity for the mesh dedup planes: the
        per-block ``u_cap`` scaled to the data shard's whole substep (the
        collective planes dedup at SUBSTEP granularity, not kernel-block),
        clamped to the shard's slot count and rounded up to a sublane
        multiple. ``mesh_u_cap`` overrides the auto-scale."""
        override = self.config.get_int("mesh_u_cap", 0)
        if override:
            return override
        from swiftsnails_tpu.parallel.mesh import DATA_AXIS

        d = self.mesh.shape[DATA_AXIS]
        pc = self._effective_pc(n)
        local_slots = (n * 2 * self.window + (n // pc) * self.pool_size) // d
        blocks = max((n // d) // pc, 1)
        cap = min(self.u_cap * blocks, local_slots)
        return max(-(-cap // 8) * 8, 8)

    def _effective_pc(self, n: int | None = None) -> int:
        """The grouped kernels' EFFECTIVE centers-per-block: the largest
        divisor of the per-substep batch ``n`` (default ``batch_size``) not
        exceeding ``centers_per_block`` — the same trace-time shrink the
        grouped substeps apply, shared so the block-ordered sampler and the
        kernel can never disagree on block granularity."""
        n = self.batch_size if n is None else n
        pc = min(self.centers_per_block, n)
        while n % pc:
            pc -= 1
        return pc

    def _dpull(self, table_state, rows, tbl=None):
        """Dense-plane pull: pjit store gather, or the hybrid dense twin."""
        from swiftsnails_tpu.parallel.hybrid import is_hybrid, pull_hybrid

        with self._tbl_scope(tbl):
            if is_hybrid(table_state):
                return pull_hybrid(self.mesh, table_state, rows,
                                   comm_dtype=self.comm_dtype)
            return pull(table_state, rows)

    def _dpush(self, table_state, rows, grads, lr, seed=None, tbl=None):
        from swiftsnails_tpu.parallel.hybrid import is_hybrid, push_hybrid

        with self._tbl_scope(tbl):
            if is_hybrid(table_state):
                return push_hybrid(self.mesh, table_state, rows, grads,
                                   self.access, lr, comm_dtype=self.comm_dtype,
                                   seed=seed)
            return push(table_state, rows, grads, self.access, lr)

    def _substep_dense(self, state: W2VState, centers, contexts, rng, lr,
                       negs=None):
        """Reference-faithful substep: per-pair negatives, 2-D tables.
        ``negs`` (tier mode) carries host-pre-sampled, slot-remapped
        negatives; the in-jit sampling below is skipped."""
        b = centers.shape[0]
        k = self.negatives
        if negs is None:
            negs = alias_sample(self.neg_alias, rng, (b, k))
        in_rows = self._step_rows(centers)
        out_rows = self._step_rows(self._id_cat(contexts, negs.reshape(-1)))

        v = self._dpull(state.in_table, in_rows, tbl="in")
        u = self._dpull(state.out_table, out_rows, tbl="out")

        def loss_of(v, u):
            return sgns_loss(v, u[:b], u[b:].reshape(b, k, -1))

        loss, (dv, du) = jax.value_and_grad(loss_of, argnums=(0, 1))(v, u)
        seed = self._comm_seed(rng)
        in_table = self._dpush(state.in_table, in_rows, dv, lr, seed=seed,
                               tbl="in")
        out_table = self._dpush(state.out_table, out_rows, du, lr, seed=seed,
                                tbl="out")
        return W2VState(in_table, out_table), loss, jnp.int32(0)

    def _substep_packed(self, state: W2VState, centers, contexts, rng, lr,
                        negs=None):
        """Fast substep: packed tables, row-DMA pull/push, pooled negatives.

        Each block of ``pool_block`` consecutive pairs shares ``pool_size``
        negatives; the pair x pool scores are one MXU matmul per block
        (einsum below) and the SGNS negative term is weighted by
        ``negatives / pool_size`` so the expected gradient matches K
        independent draws. Row traffic per pair drops from 2(1+K) rows to
        ~2(2 + pool/block) — the difference between an issue-bound scatter
        and the MXU doing the work.
        """
        b = centers.shape[0]
        # largest divisor of b not exceeding pool_block (b is static under
        # jit, so this runs at trace time; non-divisible batches still work)
        pb = min(self.pool_block, b)
        while b % pb:
            pb -= 1
        nb = b // pb
        pn = self.pool_size
        lam = self.negatives / pn
        pools = alias_sample(self.neg_alias, rng, (nb, pn)) if negs is None else negs
        in_rows = self._step_rows(centers)
        pos_rows = self._step_rows(contexts)
        pool_rows = self._step_rows(pools.reshape(-1))
        out_rows = self._id_cat(pos_rows, pool_rows)

        v = self._ppull(state.in_table, in_rows, tbl="in")
        u = self._ppull(state.out_table, out_rows, tbl="out")
        u_pos = u[:b]
        pool = u[b:].reshape(nb, pn, *u.shape[1:])

        def loss_of(v, u_pos, pool):
            pos = jnp.einsum("bsl,bsl->b", v, u_pos, preferred_element_type=jnp.float32)
            vb = v.reshape(nb, pb, *v.shape[1:])
            neg = jnp.einsum(
                "npsl,nqsl->npq", vb, pool, preferred_element_type=jnp.float32
            )
            return -(
                jax.nn.log_sigmoid(pos).mean()
                + lam * jax.nn.log_sigmoid(-neg).sum(axis=-1).mean()
            )

        loss, (dv, du_pos, dpool) = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
            v, u_pos, pool
        )
        du = jnp.concatenate([du_pos, dpool.reshape(-1, *dpool.shape[2:])])
        seed = self._comm_seed(rng)
        in_table, d1 = self._ppush(state.in_table, in_rows, dv, lr, seed=seed,
                                   tbl="in")
        out_table, d2 = self._ppush(state.out_table, out_rows, du, lr,
                                    seed=seed, tbl="out")
        return W2VState(in_table, out_table), loss, d1 + d2

    def _substep_fused(self, state: W2VState, centers, contexts, rng, lr):
        """Single-kernel hogwild substep (see ops/fused_sgns.py)."""
        from swiftsnails_tpu.ops import rowdma
        from swiftsnails_tpu.ops.fused_sgns import fused_sgns_step

        b = centers.shape[0]
        pb = min(self.pool_block, b)
        while b % pb:
            pb -= 1
        nb = b // pb
        pn = self.pool_size
        pools = alias_sample(self.neg_alias, rng, (nb, pn))
        in_t, out_t, loss = fused_sgns_step(
            state.in_table.table,
            state.out_table.table,
            self._rows(centers),
            self._rows(contexts),
            self._rows(pools.reshape(-1)),
            lr=lr,
            lam=self.negatives / pn,
            pairs_per_block=pb,
            pool_size=pn,
            interpret=not rowdma.on_tpu(),
        )
        return W2VState(
            PackedTableState(table=in_t, slots=state.in_table.slots),
            PackedTableState(table=out_t, slots=state.out_table.slots),
        ), loss, jnp.int32(0)

    def _substep_grouped(self, state: W2VState, centers, ctxs, rng, lr):
        """Center-major single-kernel hogwild substep (fused_sgns_grouped);
        with ``resident: 1`` the head rows stay VMEM-resident
        (fused_sgns_resident_step)."""
        from swiftsnails_tpu.ops import rowdma
        from swiftsnails_tpu.ops.fused_sgns import (
            effective_hot_rows,
            fused_sgns_dedup_resident_step,
            fused_sgns_dedup_step,
            fused_sgns_grouped_step,
            fused_sgns_resident_step,
        )

        n = centers.shape[0]
        # largest divisor of n not exceeding centers_per_block (static under
        # jit), so small test batches work unchanged
        pc = self._effective_pc(n)
        nb = n // pc
        pn = self.pool_size
        with phase_scope("prep"):  # the negatives' draw, ids to rows
            pools = alias_sample(self.neg_alias, rng, (nb, pn))
            ctx_rows = jnp.where(
                ctxs >= 0, self._rows(jnp.maximum(ctxs, 0)), -1
            )  # hash real ids only; pads stay -1
            center_rows = self._rows(centers)
            pool_rows = self._rows(pools.reshape(-1))
        # resident needs >= 8 hot rows after clipping to capacity
        hot_n = min(self.hot_rows, self.capacity)
        if self.dedup and self.resident and hot_n >= 8:
            # the composed kernel requires u_cap >= effective hot rows (hot
            # entries rank first into the unique list); clamp the head to
            # what the list can hold instead of raising at the first step,
            # mirroring the eff<8 grouped fallback below
            eff, _ = effective_hot_rows(hot_n, self.capacity)
            if self.u_cap < eff:
                clamped, _ = effective_hot_rows(
                    min(hot_n, self.u_cap), self.capacity)
                logging.getLogger(__name__).warning(
                    "dedup+resident with u_cap=%d < effective hot_rows=%d: "
                    "clamping the resident head to %d rows (raise u_cap to "
                    "keep the full head)", self.u_cap, eff, clamped)
                hot_n = clamped
        if self.dedup and self.resident and hot_n >= 8:
            step_fn = functools.partial(
                fused_sgns_dedup_resident_step, u_cap=self.u_cap,
                hot_rows=hot_n,
            )
        elif self.dedup:
            step_fn = functools.partial(fused_sgns_dedup_step, u_cap=self.u_cap)
        elif self.resident and hot_n >= 8:
            step_fn = functools.partial(
                fused_sgns_resident_step, hot_rows=hot_n
            )
        else:
            step_fn = fused_sgns_grouped_step
        in_t, out_t, loss = step_fn(
            state.in_table.table,
            state.out_table.table,
            center_rows,
            ctx_rows,
            pool_rows,
            lr=lr,
            lam=self.negatives / pn,
            window=self.window,
            centers_per_block=pc,
            pool_size=pn,
            interpret=not rowdma.on_tpu(),
        )
        return W2VState(
            PackedTableState(table=in_t, slots=state.in_table.slots),
            PackedTableState(table=out_t, slots=state.out_table.slots),
        ), loss, jnp.int32(0)

    def _substep_grouped_mesh(self, state: W2VState, centers, ctxs, rng, lr):
        """Center-major collective substep — the grouped plane under a mesh.

        The single-kernel grouped/resident substeps need both whole tables on
        one chip; with row-sharded tables the same center-major traffic cut
        runs through the shard_map transfer planes instead: pull each center
        row ONCE per window (vs once per pair on the flat path), score the
        whole window + shared pool against it on the MXU, push one merged
        center gradient. Row movement inside each shard is the row-DMA
        kernel plane (pull_collective_packed / _ppush, which also honors
        push_mode: bucketed); cross-shard movement is one psum over `model`
        per pull and one all_gather over `data` per push — the same
        collectives as the reference's pull/push RPC fan-out
        (global_pull_access.h:40-55, global_push_access.h:36-53).

        Pads (ctx slot -1) ride as row id == capacity: no shard owns them,
        so they pull zeros and their (mask-zeroed) gradients are dropped on
        push. Semantics are the DETERMINISTIC merged update (merge_push_value
        parity), not the kernel's hogwild — strictly closer to the faithful
        path. ``resident: 1`` has no mesh meaning (VMEM residency is
        per-chip) and quietly uses this plane.

        ``dedup: 1`` keeps its traffic cut here (VERDICT r4 #4): the
        out-table pull/push route through the shard-local unique-list
        planes (transfer.pull/push_collective_packed_dedup) — each data
        shard moves each distinct context/pool row once per substep instead
        of once per slot, the collective translation of the reference's
        per-server key grouping (global_pull_access.h:58-72). Distinct rows
        beyond :meth:`_mesh_u_cap` overflow (zero pull / dropped grad) and
        surface in the ``dedup_dropped`` metric (``push_dropped`` when
        combined with bucketed push, which subsumes the push-side dedup).

        Split into :meth:`_pull_grouped_mesh` + :meth:`_push_grouped_mesh`
        so the ``overlap: 1`` macro-step can pipeline substep i's push with
        substep i+1's pull (see :meth:`_overlap_macro`).
        """
        pulled = self._pull_grouped_mesh(state, centers, ctxs, rng)
        return self._push_grouped_mesh(state, pulled, lr)

    def _pull_grouped_mesh(self, state: W2VState, centers, ctxs, rng):
        """Pull half of the grouped collective substep: sample pools, build
        the row sets, pull both tables. Returns the ``pulled`` bundle the
        push half consumes (a pytree with config-static structure, so it can
        ride a ``lax.scan`` carry for the overlap schedule)."""
        n = centers.shape[0]
        cw = ctxs.shape[1]
        pc = self._effective_pc(n)
        nb = n // pc
        pn = self.pool_size
        pools = alias_sample(self.neg_alias, rng, (nb, pn))

        cap = self.capacity
        center_rows = self._rows(centers)
        ctx_rows = jnp.where(ctxs >= 0, self._rows(jnp.maximum(ctxs, 0)), cap)
        pool_rows = self._rows(pools.reshape(-1))
        mask = (ctxs >= 0).astype(jnp.float32)  # [n, cw]

        from swiftsnails_tpu.parallel.hybrid import is_hybrid

        v = self._ppull(state.in_table, center_rows, tbl="in")  # [n, S, L]
        out_pull_rows = self._id_cat(ctx_rows.reshape(-1), pool_rows)
        d_pull = jnp.int32(0)
        u_index = None
        hybrid = is_hybrid(state.out_table)
        if self.dedup or hybrid:
            # hybrid rides the same unique-list plane (its tail pull IS a
            # dedup pull at the coverage-sized cap); keep the (uniq, inv)
            # index so the push half skips the duplicate sort
            cap = self._out_u_cap(n, out_pull_rows.shape[0], hybrid)
            with self._tbl_scope("out"):
                if hybrid:
                    from swiftsnails_tpu.parallel.hybrid import (
                        pull_hybrid_packed,
                    )

                    u_all, u_index, d_pull = pull_hybrid_packed(
                        self.mesh, state.out_table, out_pull_rows, cap,
                        comm_dtype=self.comm_dtype)
                else:
                    from swiftsnails_tpu.parallel.transfer import (
                        pull_collective_packed_dedup,
                    )

                    u_all, u_index, d_pull = pull_collective_packed_dedup(
                        self.mesh, state.out_table, out_pull_rows, cap,
                        comm_dtype=self.comm_dtype)
        else:
            u_all = self._ppull(state.out_table, out_pull_rows, tbl="out")
        seed = self._comm_seed(rng)
        return (center_rows, out_pull_rows, mask, v, u_all, u_index, d_pull,
                seed)

    def _out_u_cap(self, n: int, out_rows: int, hybrid: bool) -> int:
        """Unique capacity for the grouped plane's out-table dedup pull:
        the dedup lane's slot-scaled cap, the hybrid coverage cap, or the
        min of both when they compose."""
        caps = []
        if self.dedup:
            caps.append(self._mesh_u_cap(n))
        if hybrid:
            caps.append(self._hybrid_cap(out_rows))
        return min(caps)

    def _push_grouped_mesh(self, state: W2VState, pulled, lr):
        """Push half: SGNS loss/grads on the pulled rows, merged push of both
        tables. Shapes/constants rederive from the bundle, so the math is
        identical whether it runs fused with its own pull (plain substep) or
        against a one-substep-stale pull (overlap schedule)."""
        (center_rows, out_pull_rows, mask, v, u_all, u_index, d_pull,
         seed) = pulled
        n, cw = mask.shape
        pc = self._effective_pc(n)
        nb = n // pc
        pn = self.pool_size
        lam = self.negatives / pn
        inv_b = 1.0 / (n * (self.window + 1))
        u = u_all[: n * cw].reshape((n, cw) + u_all.shape[1:])
        q = u_all[n * cw :].reshape((nb, pn) + u_all.shape[1:])

        def loss_of(v, u, q):
            pos = jnp.einsum("ncsl,nsl->nc", u, v,
                             preferred_element_type=jnp.float32)
            vb = v.reshape((nb, pc) + v.shape[1:])
            neg = jnp.einsum("npsl,nqsl->npq", vb, q,
                             preferred_element_type=jnp.float32)
            n_real = mask.sum(axis=1).reshape(nb, pc, 1)  # pool weight/center
            return -inv_b * (
                jnp.sum(jax.nn.log_sigmoid(pos) * mask)
                + lam * jnp.sum(jax.nn.log_sigmoid(-neg) * n_real)
            )

        loss, (dv, du, dq) = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(v, u, q)
        # du is data-batch lineage, dq rng-sample lineage: the same
        # mixed-lineage concat GSPMD mis-assembles (see _mesh_safe_cat)
        out_grads = self._mesh_safe_cat(
            [du.reshape((n * cw,) + du.shape[2:]),
             dq.reshape((nb * pn,) + dq.shape[2:])])
        from swiftsnails_tpu.parallel.hybrid import is_hybrid

        hybrid = is_hybrid(state.out_table)
        in_table, d1 = self._ppush(state.in_table, center_rows, dv, lr,
                                   seed=seed, tbl="in")
        if (self.dedup or hybrid) and self.push_mode != "bucketed":
            # reuse the pull's unique index: skips the duplicate sort and
            # keeps the overflow metric single-counted (d2 is 0 here)
            cap = self._out_u_cap(n, out_pull_rows.shape[0], hybrid)
            with self._tbl_scope("out"):
                if hybrid:
                    from swiftsnails_tpu.parallel.hybrid import (
                        push_hybrid_packed,
                    )

                    out_table, d2 = push_hybrid_packed(
                        self.mesh, state.out_table, out_pull_rows, out_grads,
                        self.access, lr, cap, index=u_index,
                        comm_dtype=self.comm_dtype, seed=seed,
                        zero=self.zero)
                else:
                    from swiftsnails_tpu.parallel.transfer import (
                        push_collective_packed_dedup,
                    )

                    out_table, d2 = push_collective_packed_dedup(
                        self.mesh, state.out_table, out_pull_rows, out_grads,
                        self.access, lr, cap, index=u_index,
                        comm_dtype=self.comm_dtype, seed=seed)
        else:
            out_table, d2 = self._ppush(state.out_table, out_pull_rows,
                                        out_grads, lr, seed=seed, tbl="out")
        return W2VState(in_table, out_table), loss, d_pull + d1 + d2

    def _overlap_macro(self, state: W2VState, c, x, keys, lr):
        """Software-pipelined macro-step over the grouped mesh plane.

        ``overlap: 1`` — each scan iteration issues substep i+1's pull
        against the PRE-push tables and substep i's push with no data
        dependence between the two, so XLA is free to emit async
        ``-start``/``-done`` collective pairs that run the push all_gather
        under the next pull + compute (the 2204.06514 overlap lever).

        ``overlap: 2`` — a true two-deep software pipeline (the MPMD
        pipelining shape of arXiv 2412.14374 collapsed onto one program):
        the carry double-buffers TWO in-flight pulled bundles, so the pull
        collective issued for substep i+2 has a FULL substep of compute
        (substep i's grads + push) between its -start and the iteration
        that consumes it — not just the tail of its own iteration. Composes
        with dedup/bucketed/comm_dtype/zero unchanged: the substep math is
        identical, only consumption is deferred one more iteration.

        Semantics: substep i reads rows that miss the last ``depth``
        substeps' updates — stale-by-``depth`` async SGD, the reference
        worker's pipeline behavior (pulls for upcoming batches outstanding
        while push callbacks are in flight, transfer.h:55-268). The final
        ``depth`` iterations prefetch wrapped-around substeps to keep
        shapes static; those pulls are discarded (``depth/t`` overhead).
        """
        t = c.shape[0]
        depth = min(self.overlap, t)
        warm = [self._pull_grouped_mesh(state, c[i], x[i], keys[i])
                for i in range(depth)]
        nxt = (jnp.roll(c, -depth, axis=0), jnp.roll(x, -depth, axis=0),
               jnp.roll(keys, -depth, axis=0))

        if depth <= 1:
            def body(carry, xs):
                st, pulled = carry
                cn, xn, kn = xs
                pulled_next = self._pull_grouped_mesh(st, cn, xn, kn)
                st, loss, dropped = self._push_grouped_mesh(st, pulled, lr)
                return (st, pulled_next), (loss, dropped)

            (state, _), (losses, drops) = jax.lax.scan(
                body, (state, warm[0]), nxt)
            return state, losses, drops

        def body(carry, xs):
            st, p0, p1 = carry
            cn, xn, kn = xs
            p2 = self._pull_grouped_mesh(st, cn, xn, kn)
            st, loss, dropped = self._push_grouped_mesh(st, p0, lr)
            return (st, p1, p2), (loss, dropped)

        (state, _, _), (losses, drops) = jax.lax.scan(
            body, (state, warm[0], warm[1]), nxt)
        return state, losses, drops

    def _substep_packed_perpair(self, state: W2VState, centers, contexts,
                                rng, lr, negs=None):
        """Packed tables with reference-faithful per-pair K negatives."""
        b = centers.shape[0]
        k = self.negatives
        if negs is None:
            negs = alias_sample(self.neg_alias, rng, (b, k))
        in_rows = self._step_rows(centers)
        out_rows = self._step_rows(self._id_cat(contexts, negs.reshape(-1)))

        v = self._ppull(state.in_table, in_rows, tbl="in")
        u = self._ppull(state.out_table, out_rows, tbl="out")
        u_pos = u[:b]
        u_neg = u[b:].reshape(b, k, *u.shape[1:])

        def loss_of(v, u_pos, u_neg):
            pos = jnp.einsum("bsl,bsl->b", v, u_pos, preferred_element_type=jnp.float32)
            neg = jnp.einsum("bsl,bksl->bk", v, u_neg, preferred_element_type=jnp.float32)
            return -(
                jax.nn.log_sigmoid(pos) + jax.nn.log_sigmoid(-neg).sum(axis=-1)
            ).mean()

        loss, (dv, du_pos, du_neg) = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
            v, u_pos, u_neg
        )
        du = jnp.concatenate([du_pos, du_neg.reshape(-1, *du_neg.shape[2:])])
        seed = self._comm_seed(rng)
        in_table, d1 = self._ppush(state.in_table, in_rows, dv, lr, seed=seed,
                                   tbl="in")
        out_table, d2 = self._ppush(state.out_table, out_rows, du, lr,
                                    seed=seed, tbl="out")
        return W2VState(in_table, out_table), loss, d1 + d2

    def train_step(self, state: W2VState, batch, rng):
        """One dispatch = ``steps_per_call`` optimizer substeps under lax.scan."""
        centers, contexts = batch["centers"], batch["contexts"]
        n = centers.shape[0]
        t = max(n // self.batch_size, 1)
        b = n // t
        if self.fused and self.grouped:
            substep = (
                self._substep_grouped_mesh
                if self.mesh is not None
                else self._substep_grouped
            )
        elif self.fused:
            # flat fused has no collective plane; under a mesh the pooled
            # packed substep is its equivalent (same math, transfer plane)
            substep = (
                self._substep_packed if self.mesh is not None
                else self._substep_fused
            )
        elif self.packed:
            substep = (
                self._substep_packed
                if self.neg_mode == "pool"
                else self._substep_packed_perpair
            )
        else:
            substep = self._substep_dense

        # word2vec.c linear decay: lr * max(1 - progress, 1e-4). progress is
        # a replicated scalar supplied by batches(); constant within one
        # dispatch (the per-substep refinement is below batch granularity).
        if self.lr_decay and "progress" in batch:
            lr = self.lr * jnp.maximum(1.0 - batch["progress"], 1e-4)
        else:
            lr = self.lr

        def metrics_of(loss, dropped):
            m = {"loss": loss}
            if self.push_mode == "bucketed":
                m["push_dropped"] = dropped
            elif self.dedup and self.mesh is not None:
                m["dedup_dropped"] = dropped
            elif self.placement_cut and self.mesh is not None:
                # hybrid tail unique-capacity overflow (coverage-sized cap)
                m["hybrid_dropped"] = dropped
            return m

        # table_tier: host — negatives were sampled host-side by tier_plan
        # (bit-identical RNG derivation) and arrive in the batch already
        # hashed and remapped to cache-slot space, like centers/contexts.
        negs_all = batch.get("negs") if self.tiered else None

        if t == 1:
            # only the tier-capable substeps accept negs=; the grouped-mesh
            # and overlap paths (tiered rejects them) keep their signature
            if negs_all is not None:
                state, loss, dropped = substep(
                    state, centers, contexts, rng, lr, negs=negs_all)
            else:
                state, loss, dropped = substep(state, centers, contexts, rng, lr)
            return state, metrics_of(loss, dropped)

        keys = jax.random.split(rng, t)
        c_t = centers.reshape(t, b)
        x_t = contexts.reshape((t, b) + contexts.shape[1:])
        if self.mesh is not None:
            c_t, x_t = self._shard_substeps(c_t, x_t)
        on_grouped_mesh = (
            self.fused and self.grouped and self.mesh is not None
        )
        if self.overlap and on_grouped_mesh:
            state, losses, drops = self._overlap_macro(state, c_t, x_t, keys, lr)
            return state, metrics_of(losses.mean(), drops.sum())

        if negs_all is not None:
            per = negs_all.shape[0] // t
            n_t = negs_all.reshape((t, per) + negs_all.shape[1:])

            def body(st, xs):
                c, x, key, ng = xs
                st, loss, dropped = substep(st, c, x, key, lr, negs=ng)
                return st, (loss, dropped)

            state, (losses, drops) = jax.lax.scan(
                body, state, (c_t, x_t, keys, n_t))
            return state, metrics_of(losses.mean(), drops.sum())

        def body(st, xs):
            c, x, key = xs
            st, loss, dropped = substep(st, c, x, key, lr)
            return st, (loss, dropped)

        state, (losses, drops) = jax.lax.scan(body, state, (c_t, x_t, keys))
        return state, metrics_of(losses.mean(), drops.sum())

    # -- tiered parameter store (table_tier: host; see tiered/) -------------

    def _plan_rows(self, keys: np.ndarray) -> np.ndarray:
        """Host-side twin of :meth:`_rows`: eager hash (same jit-able
        ``hash_row``, threefry-free, deterministic eager-vs-traced) so the
        tier planner sees the exact row ids the resident substep would."""
        keys = np.asarray(keys)
        if self.hash_keys:
            return np.asarray(hash_row(jnp.asarray(keys), self.capacity))
        return keys.astype(np.int32, copy=False)

    def tier_spec(self):
        if not self.tiered:
            return None
        layout = "packed" if self.packed else "dense"
        return {
            "in_table": {"layout": layout, "group": 1},
            "out_table": {"layout": layout, "group": 1},
        }

    def table_geometry(self):
        layout = "packed" if self.packed else "dense"
        geo = {"layout": layout, "group": 1, "dim": self.dim,
               "capacity": self.capacity}
        return {"in_table": dict(geo), "out_table": dict(geo)}

    def tier_tables(self, state: W2VState):
        return {"in_table": state.in_table, "out_table": state.out_table}

    def tier_with_tables(self, state: W2VState, tables):
        return W2VState(
            in_table=tables.get("in_table", state.in_table),
            out_table=tables.get("out_table", state.out_table),
        )

    def _tier_plan_fn(self, t: int, shape):
        """One fused, cached jit per (substeps, negative-draw shape): the
        per-step ``fold_in``, RNG split, alias sampling, and id hashing in a
        single dispatch (the step counter rides in as a uint32 operand, same
        as the step fn — no retrace, no eager threefry chain). The plan runs
        every step on the prefetch producer thread; the previous op-by-op
        eager chain (~10 dispatches, GIL-held) was the tier's single
        biggest steady-state cost on the CPU smoke."""
        fn = self._plan_fns.get((t, shape))
        if fn is None:

            def plan(root_rng, step, centers, contexts):
                rng = jax.random.fold_in(root_rng, step)
                keys = [rng] if t == 1 else list(jax.random.split(rng, t))
                negs = jnp.concatenate(
                    [alias_sample(self.neg_alias, key, shape)
                     for key in keys], axis=0)

                def rows(k):
                    if self.hash_keys:
                        return hash_row(k, self.capacity)
                    return k.astype(jnp.int32)

                return rows(centers), rows(contexts), rows(negs)

            fn = self._plan_fns[(t, shape)] = jax.jit(plan)
        return fn

    def tier_plan(self, batch, root_rng, step):
        """Host-side step plan: replicate the in-jit RNG derivation
        (``fold_in`` then ``split`` into per-substep keys, then
        ``alias_sample``) bit-exactly, hash every id, and report which
        master rows the step touches.

        Returns ``(ids, aug, remap_keys)``: per-table touched row ids, batch
        augmentations (hashed centers/contexts + the pre-sampled negatives),
        and which batch keys each table's remap applies to."""
        centers = np.asarray(batch["centers"])
        contexts = np.asarray(batch["contexts"])
        n = centers.shape[0]
        t = max(n // self.batch_size, 1)
        b = n // t
        if self.packed and self.neg_mode == "pool":
            pb = min(self.pool_block, b)
            while b % pb:
                pb -= 1
            shape = (b // pb, self.pool_size)
        else:
            shape = (b, self.negatives)
        c_d, x_d, n_d = self._tier_plan_fn(t, shape)(
            root_rng, np.uint32(step), centers, contexts)
        c_r, x_r, n_r = np.asarray(c_d), np.asarray(x_d), np.asarray(n_d)
        ids = {
            "in_table": c_r.ravel(),
            "out_table": np.concatenate([x_r.ravel(), n_r.ravel()]),
        }
        aug = {"centers": c_r, "contexts": x_r, "negs": n_r}
        remap = {"in_table": ["centers"], "out_table": ["contexts", "negs"]}
        return ids, aug, remap

    def tier_warm_rows(self):
        """Hottest-first row ids for the cache prewarm (vocab frequency
        order; both tables share the unigram distribution)."""
        order = self.vocab.hottest_rows().astype(np.int64)
        rows = np.asarray(self._plan_rows(order))
        return {"in_table": rows, "out_table": rows}

    # -- export (ServerTerminate parity: text dump of the table) -----------

    def _all_vocab_rows(self, state: W2VState) -> np.ndarray:
        ids = self._rows(jnp.arange(len(self.vocab), dtype=jnp.int32))
        if self.packed:
            vals = unpack_rows(state.in_table.table.at[ids].get(mode="promise_in_bounds"),
                               self.dim)
        else:
            vals = pull(state.in_table, ids)
        return np.asarray(vals, dtype=np.float32)  # bf16: ml_dtypes don't format

    def export_text(self, state: W2VState, path: str) -> None:
        rows = self._all_vocab_rows(state)
        with open(path, "w", encoding="utf-8") as f:
            f.write(f"{len(self.vocab)} {self.dim}\n")
            for i, word in enumerate(self.vocab.words):
                vec = " ".join(f"{x:.6f}" for x in rows[i])
                f.write(f"{word} {vec}\n")

    # -- eval: nearest neighbors for sanity checks --------------------------

    def neighbors(self, state: W2VState, word: str, topn: int = 10):
        emb = self._all_vocab_rows(state)
        norms = np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9
        emb = emb / norms
        q = emb[self.vocab.index[word]]
        sims = emb @ q
        order = np.argsort(-sims)
        return [(self.vocab.words[i], float(sims[i])) for i in order[1 : topn + 1]]
