"""Compile-once streaming engine for dynamic batch updates (tentpole).

``DynLP.step`` rebuilds and re-stages the device ``PropagationProblem``
from scratch every Δ_t — at its exact (U, K) when ``auto_bucket=False``
(a recompile on nearly every batch, the recomputation tax the paper
eliminates), and even bucketed it allocates fresh device buffers per
batch and serializes host work against the solve.  ``StreamEngine`` is
the amortized version:

  * **Bucket ladder** — every snapshot is padded up the geometric
    ``(U_bucket, K_bucket)`` ladder (``snapshot.bucket`` ×
    ``snapshot.bucket_k``), so an unbounded stream compiles the
    propagation entry point a bounded number of times
    (``snapshot.ladder_size``).
  * **Persistent donated buffers** — per bucket the engine keeps two
    generations of device buffers for ``(nbr, wgt, wl0, wl1, valid)``
    plus the ``f``/``frontier`` vectors.  Batch t+1's snapshot is
    committed into the generation *not* referenced by the in-flight
    batch t solve, with the stale generation donated so XLA recycles
    the allocation instead of growing the arena every Δ_t.
  * **Staged transfers** — ``submit``/``drain`` split the step: ``submit``
    applies Δ_t on the host, stages its topology to the device, and
    launches the solve; it only *then* blocks on the previous batch.
    Host graph update + H2D of batch t+1 overlap device propagation of
    batch t (JAX dispatch is async on every backend).

``step`` (submit + drain) keeps the exact ``DynLP.step`` semantics and
numerics — streamed labels are allclose to fresh per-batch DynLP results
(tests/test_stream.py); the solve itself routes through the backend
registry of ``kernels.ops``: the engine resolves each ladder rung's
backend once at rung entry (``backend="auto"`` may pick the ``bsr`` MXU
path on TPU when the measured post-reorder block fill factor clears the
registry's threshold), then reuses the decision for every batch in the
rung.  A ``bsr`` rung stages snapshots in the paper's Step-1 component
order (``core.components.component_order``) so the adjacency densifies
into tiles, derives the per-edge tile-slot map per Δ_t
(``kernels.bsr_spmv.ell_bsr_layout``), and compiles one tile budget per
rung — a Δ_t whose slot requirement overflows the budget runs on the
backend the registry resolves for the rung with bsr out of the scan
(``_bsr_twin``), with a once-per-rung warning, mirroring the
halo-overflow contract.

With ``mesh=`` the same stream spans a device mesh: rows of every bucket
shard over all mesh axes through the ``core.distributed`` shard_map
transport, buckets are padded to a multiple of the device count, and one
partition plan per ladder rung is reused across every batch in that rung.
``transport=`` picks the per-sweep collective: ``"allgather"`` ships
every shard's full F block (topology-free); ``"halo"`` ships only each
shard's export prefix, with the export budget compiled once per rung
(``StreamHaloPlan``) and the export row layout re-derived per Δ_t on the
host — a batch whose exports overflow the rung's budget falls back to
all-gather for that Δ_t with a logged warning.  ``"auto"`` (default)
measures the rung's export fraction at rung entry and picks halo when it
is small enough to pay; ``"auto:measured"`` instead times one real sweep
per transport at rung entry and caches the winner (two extra probe
compiles per rung — the cost of measuring reconstruct overhead the
byte-count heuristic can't see).  Labels stay bit-identical to the
single-device engine under every transport
(tests/test_stream_sharded.py, tests/test_stream_property.py); a
``bsr`` rung stages in the halo row layout under BOTH transports so its
labels are bit-identical across them too.  See docs/streaming.md
§Transports and docs/backends.md.

The ``landmark`` backend changes the STAGING, not the solve: once its
lazily-sampled landmark state is ready and the registry resolves the
engine's knob to ``"landmark"``, snapshots restrict to the hot working
set (rows touched by a Δ_t within the last ``hot_ttl`` batches), cold
unlabeled neighbors fold their committed fractional labels into the
supernode weights (an exact boundary condition — see
``core.snapshot.build_host_problem``), and each commit additionally
runs the low-rank cold pass of ``kernels.landmark_propagate`` so the
cold tail keeps moving at O(N·R).  Staged hot problems ride the same
buffers, plans and transports as every exact backend; labels carry an
agreement-floor contract instead of bit-equality (docs/backends.md,
``benchmarks/landmark_lp.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed
from repro.core.components import compact_labels, component_order
from repro.core.dynlp import gprime_components
from repro.core.init_labels import supernode_init
from repro.core.propagate import PropagationProblem
from repro.core.snapshot import (DeviceLabelView, HostSnapshot, LabelView,
                                 apply_halo_layout, bucket, bucket_k,
                                 build_host_problem, publish_device_view,
                                 reorder_host_snapshot)
from repro.core.trace import Recorder
from repro.graph import partition
from repro.graph.dynamic import UNLABELED, BatchUpdate, DynamicGraph
from repro.ingest import DeviceIngestor
from repro.kernels import ops
from repro.kernels.bsr_spmv import ell_bsr_layout
from repro.kernels.landmark_propagate import LandmarkConfig, LandmarkState

logger = logging.getLogger(__name__)

TRANSPORTS = ("allgather", "halo", "auto", "auto:measured")

# auto picks halo for a rung iff its compiled export budget would move
# at most this fraction of the full all-gather bytes per sweep.
AUTO_EXPORT_FRACTION = 0.5


@dataclasses.dataclass
class StreamStats:
    iterations: int
    converged: bool
    num_components: int
    frontier_size: int
    num_unlabeled: int
    wall_ms: float
    max_residual: float
    bucket: tuple[int, int]  # (U_bucket, K_bucket) device shape this Δ_t;
    # (0, 0) for a no-op Δ_t whose empty frontier staged nothing
    recompiled: bool  # True iff this Δ_t triggered any XLA compile
    transport: str = "single"  # collective this Δ_t rode: "single" (no
    # mesh), "allgather", "halo", or "none" (no-op Δ_t, nothing solved)
    backend: str = "none"  # registry backend that solved this Δ_t
    # ("ref"/"ell_pallas"/"bsr"/"landmark"; "none" for a no-op Δ_t) — a
    # bsr rung's slot-budget overflow shows up here as its twin's name
    # (``StreamEngine._bsr_twin``); a "landmark" batch solved the hot
    # working set only


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt(old: PropagationProblem, new: PropagationProblem) -> PropagationProblem:
    """Copy ``new`` into ``old``'s (donated) device storage."""
    return new


@dataclasses.dataclass
class _Pending:
    res: object  # PropagateResult (device, possibly still in flight);
    # None for a no-op batch whose frontier was empty (nothing to solve)
    unl_ids: np.ndarray
    t0: float
    num_components: int
    frontier_size: int
    bucket: tuple[int, int]
    recompiled: bool
    # Post-batch host state captured at submit (after the previous drain
    # folded its labels in): becomes the committed LabelView at drain,
    # with this batch's solved rows folded over view_f.
    view_labels: np.ndarray
    view_alive: np.ndarray
    view_f: np.ndarray
    transport: str = "single"
    backend: str = "none"
    # row-layout inverse (halo export-prefix or BSR component order):
    # solved row for original row i is rows[i] (None = staged unpermuted)
    rows: np.ndarray | None = None
    # landmark batches only: the cold unlabeled rows excluded from the
    # staged hot problem — drain serves them through the low-rank pass
    cold_ids: np.ndarray | None = None
    # rows a LATER batch relabelled while this one was in flight: that
    # relabel already reset their f, and the sequential order (solve, then
    # relabel) says it wins, so drain must not overwrite them
    relabelled: np.ndarray | None = None
    dispatched_at: float = 0.0  # perf_counter at the end of its submit


@dataclasses.dataclass
class _Staging:
    """One Δ_t's resolved staging decision (plan, layout, backend)."""

    staged: HostSnapshot  # possibly row-permuted
    backend: str  # registry backend solving this Δ_t
    transport: str  # "single" | "allgather" | "halo"
    plan: object | None = None  # StreamShardPlan/StreamHaloPlan (mesh only)
    rows: np.ndarray | None = None  # old row -> staged row (fold-back)
    perm: np.ndarray | None = None  # staged row -> old row (f0/frontier)
    slot: np.ndarray | None = None  # bsr per-edge tile-slot map
    num_slots: int = 0  # bsr compiled tile budget (0 otherwise)


class StreamEngine:
    """Stateful compile-once streaming DynLP over a ``DynamicGraph``."""

    def __init__(
        self,
        graph: DynamicGraph,
        delta: float = 1e-4,
        tau: float | None = None,
        max_iters: int = 200_000,
        max_degree: int | None = None,
        backend: str | None = None,
        block_rows: int = 512,
        interpret: bool | None = None,
        mesh: jax.sharding.Mesh | None = None,
        max_k: int | None | str = "auto",
        transport: str | None = None,
        read_placement: object = "auto",
        ingest: object = None,
        landmark: object = None,
        ingest_order: str = "arrival",
    ):
        self.graph = graph
        # ingest: who nominates kNN candidates for arriving batches.
        # None/"host" = the blockwise host staging path (graph default);
        # "device" = a DeviceIngestor running the Pallas/XLA argkmin
        # kernel over the device-resident embedding store
        # (docs/ingestion.md), adopting any rows already in the graph;
        # or pass a pre-built selector instance.  Either way the labels
        # and topology are bit-identical — only where the candidate
        # search runs changes.  With a mesh, "device" picks the
        # row-sharded store automatically (move-the-batch argkmin,
        # docs/ingestion.md §Sharded store) — same labels/topology again,
        # the store just spreads over the mesh's HBM.
        if ingest in (None, "host"):
            self.ingestor = None
        elif ingest == "device":
            self.ingestor = DeviceIngestor(graph.emb_dim, mesh=mesh)
            if graph.num_nodes:
                self.ingestor.attach(graph)
        elif isinstance(ingest, str):
            raise ValueError(f"unknown ingest mode {ingest!r}; want "
                             "'host', 'device', or a selector instance")
        else:
            self.ingestor = ingest
        # ingest_order: how an arriving batch's rows are ordered before id
        # assignment.  "arrival" keeps the caller's order; "locality" runs
        # data.synth.cosine_locality_order over each admitted batch so
        # consecutive ids are angular neighbors — ids land halo-friendly
        # (fewer cross-shard references ⇒ smaller export prefixes; the
        # top-rung export-fraction delta is recorded in BENCH_ingest.json).
        # Reordering happens before ids exist, so engines that share a
        # stream agree bit-for-bit as long as they share this knob.
        if ingest_order not in ("arrival", "locality"):
            raise ValueError(f"unknown ingest_order {ingest_order!r}; want "
                             "'arrival' or 'locality'")
        self.ingest_order = ingest_order
        self.delta = delta
        self.tau = tau
        self.max_iters = max_iters
        self.max_degree = max_degree
        self.backend = backend
        self.block_rows = block_rows
        self.interpret = interpret
        # mesh: shard the stream — rows of every bucket are partitioned
        # over ALL mesh axes (core.distributed shard_map transport); row
        # buckets are padded to a multiple of the device count so each
        # rung shards evenly, and one partition plan per rung is reused
        # across every batch that lands in it.
        self.mesh = mesh
        # transport: per-sweep collective of the sharded solve.  An
        # explicit "halo" demands a mesh; when left unset the
        # REPRO_STREAM_TRANSPORT env var replaces the "auto" default —
        # as a fleet-wide hint it is simply ignored on mesh-less engines
        # (mirroring the REPRO_BACKEND degrade semantics).
        if transport is not None and transport not in TRANSPORTS:
            raise ValueError(f"unknown transport {transport!r}; want one "
                             f"of {TRANSPORTS}")
        if transport == "halo" and mesh is None:
            raise ValueError("transport='halo' requires mesh= (a "
                             "single-device stream has no collective)")
        if transport is None:
            transport = os.environ.get("REPRO_STREAM_TRANSPORT", "auto")
            if transport not in TRANSPORTS:
                raise ValueError(
                    f"REPRO_STREAM_TRANSPORT={transport!r} invalid; want "
                    f"one of {TRANSPORTS}")
        self.transport = transport
        # max_k: cap the ELL neighbor axis (heaviest-edge truncation) so a
        # hub vertex can't drag the K-bucket ladder up (core.snapshot).
        # Default "auto" = 4x the graph's kNN k (measured at parity on
        # hub-heavy synthetics, BENCH_stream.json max_k_accuracy); pass
        # max_k=None to stream untruncated.
        if isinstance(max_k, str) and max_k != "auto":
            raise ValueError(
                f"max_k={max_k!r} invalid; want an int, None (uncapped), "
                "or 'auto' (4x the graph's kNN k)")
        self.max_k = 4 * graph.k if max_k == "auto" else max_k
        # Pin the backend knob at construction: the fleet-wide
        # REPRO_BACKEND hint is read ONCE here — row padding and the
        # candidate set below depend on it, so a mid-stream env flip must
        # not hand a later rung a backend the engine never prepared for
        # (rung resolution passes use_env=False).  A hint with no
        # sharded form degrades to auto, mirroring select_backend.
        knob = backend
        if knob in (None, "auto"):
            env = os.environ.get("REPRO_BACKEND", "auto")
            knob = (env if env != "auto" and (
                mesh is None or ops.backend_spec(env).sharded) else "auto")
        self._backend_knob = knob
        # The registry tells us up front which backends the pinned knob
        # could ever resolve to; only when bsr is among them do we pay
        # block-size row padding and per-rung fill measurement.
        self._backend_candidates = (
            ops.backend_candidates(None, sharded=mesh is not None)
            if knob == "auto" else (ops.backend_spec(knob).name,))
        self._bsr_block = ops.bsr_block_size()
        # landmark: configuration of the approximate hot/cold backend
        # (kernels.landmark_propagate).  None = off, unless the pinned
        # knob names "landmark" — then a default config activates (the
        # knob is meaningless without the state); True = default config;
        # a dict or LandmarkConfig tunes it.  With backend="auto" and a
        # config, the registry may pick landmark per its eligibility rule
        # (LANDMARK_AUTO_MIN_ROWS) once the state is ready; the decision
        # then LATCHES for the engine's lifetime so every later rung
        # carries one consistent contract (docs/backends.md).
        if landmark is None and knob == "landmark":
            landmark = True
        if landmark is True:
            landmark = LandmarkConfig()
        elif isinstance(landmark, dict):
            landmark = LandmarkConfig(**landmark)
        self._lm = (LandmarkState(landmark, graph.emb_dim)
                    if landmark is not None else None)
        self._lm_streaming = False  # the hot/cold latch (see above)
        # batch index each vertex was last touched by a Δ_t — the hot
        # working set is everything with age <= hot_ttl
        self._touched_at = np.full(graph.num_nodes, -1, np.int64)
        self.landmark_batches = 0  # batches solved on the hot/cold split
        self.landmark_cold_rows = 0  # cold rows served by the low-rank pass
        row_multiple = int(mesh.devices.size) if mesh is not None else 1
        if "bsr" in self._backend_candidates:
            # every shard's row block must tile evenly into BSR block rows
            row_multiple *= self._bsr_block
        self._row_multiple = row_multiple if row_multiple > 1 else None
        self._plans: dict[tuple, distributed.StreamShardPlan] = {}
        self._halo_plans: dict[tuple, distributed.StreamHaloPlan] = {}
        self.plan_builds = 0  # partition plans built — ≤ rungs touched
        # per-rung transport state: mode fixed at rung entry ("halo" or
        # "allgather"), export budget compiled into the rung's halo plan
        self._transport_modes: dict[tuple[int, int], str] = {}
        self._export_budgets: dict[tuple[int, int], int] = {}
        self._overflow_warned: set[tuple[int, int]] = set()
        self.halo_batches = 0  # batches solved on the halo transport
        self.transport_overflows = 0  # halo batches forced onto all-gather
        # per-rung backend state (registry decision fixed at rung entry)
        # and the bsr tile-slot budget compiled into the rung's runner
        self._backend_modes: dict[tuple[int, int], str] = {}
        self._slot_budgets: dict[tuple[int, int], int] = {}
        self._slot_overflow_warned: set[tuple[int, int]] = set()
        self.bsr_batches = 0  # batches solved on the bsr backend
        self.backend_overflows = 0  # bsr batches forced onto the twin
        self._measured: dict[tuple[int, int], dict] = {}  # auto:measured
        # rungs whose auto:measured decision came from a PERSISTED probe
        # cache (core.persistence) instead of a fresh timed sweep
        self.probe_cache_hits = 0
        # per-engine max_k truncation-warning dedup (a fresh engine warns
        # again instead of inheriting another engine's state)
        self._max_k_warned: set[tuple[int, int]] = set()
        # bucket_key -> two generations of device problem buffers; the
        # generation toggles per commit so the in-flight solve never shares
        # storage with the snapshot being staged.
        self._buffers: dict[tuple[int, int], list[PropagationProblem | None]] = {}
        self._gen: dict[tuple[int, int], int] = {}
        self._pending: _Pending | None = None
        self.bucket_keys: set[tuple[int, int]] = set()
        self.recompile_count = 0  # batches that triggered any XLA compile
        self.batches = 0
        self.commits = 0  # batches whose results have been drained
        # Query-side committed snapshot (serving read path): refreshed at
        # every drain, never mutated in place — readers hold a consistent
        # view while the next batch's solve is in flight.
        self._view = LabelView.from_graph(graph, commit_id=0)
        self.last_commit_at: float | None = None  # perf_counter, end of drain
        # spans/counters of submit and drain (core.trace); LPService
        # records its admission and ack spans here too
        self.trace = Recorder()
        # Device twin of the committed view: published lazily on the
        # first ``device_view()`` call, then eagerly at every drain (the
        # H2D dispatches async, overlapping the next batch's host work).
        # ``read_placement="auto"`` resolves to the mesh's read replica /
        # row sharding (core.distributed.read_placement) or the default
        # device; pass an explicit jax.Device or Sharding to override.
        self._read_placement = (distributed.read_placement(mesh)
                                if read_placement == "auto" else read_placement)
        self._device_view: DeviceLabelView | None = None

    # ------------------------------------------------------------------ #
    def _plan_for(self, key: tuple[int, int], backend: str,
                  num_slots: int = 0) -> distributed.StreamShardPlan:
        """Partition plan for one ladder rung — built once, then reused
        for every batch whose padded snapshot lands in that rung.  A bsr
        rung's slot-budget overflow additionally builds the rung's
        twin plan (+1 plan per recorded overflow, like halo)."""
        pkey = (key, backend, num_slots)
        plan = self._plans.get(pkey)
        if plan is None:
            plan = distributed.build_stream_plan(
                self.mesh, key, backend=backend,
                delta=self.delta, max_iters=self.max_iters,
                block_rows=self.block_rows, interpret=self.interpret,
                donate=True,
                block_size=self._bsr_block if backend == "bsr" else 0,
                num_slots=num_slots if backend == "bsr" else 0)
            self._plans[pkey] = plan
            self.plan_builds += 1
        return plan

    # ------------------------------------------------------------------ #
    def _halo_plan_for(self, key: tuple[int, int], export_max: int,
                       backend: str,
                       num_slots: int = 0) -> distributed.StreamHaloPlan:
        """Halo partition plan for one ladder rung — the export budget is
        fixed at rung entry, so like the all-gather plan it is built once
        and reused for every same-rung batch."""
        hkey = (key, export_max, backend, num_slots)
        plan = self._halo_plans.get(hkey)
        if plan is None:
            plan = distributed.build_stream_halo_plan(
                self.mesh, key, export_max, backend=backend,
                delta=self.delta, max_iters=self.max_iters,
                block_rows=self.block_rows, interpret=self.interpret,
                donate=True,
                block_size=self._bsr_block if backend == "bsr" else 0,
                num_slots=num_slots if backend == "bsr" else 0)
            self._halo_plans[hkey] = plan
            self.plan_builds += 1
        return plan

    # ------------------------------------------------------------------ #
    def _resolve_rung_backend(self, key: tuple[int, int],
                              nbr_staged: np.ndarray, n_valid: int):
        """Fix the rung's backend at rung entry through the registry.

        When bsr is among the candidates the post-reorder block fill
        factor is measured from this first snapshot (already permuted
        into the order bsr would stage) and fed to the registry's
        ``auto_eligible`` predicates; an explicit/env ``"bsr"`` skips the
        eligibility question but still derives the layout, whose slot
        requirement — scaled by the rung's remaining fill factor
        ``key[0] / n_valid`` (same reasoning as
        ``graph.partition.export_budget``: a rung entered at ``n_valid``
        rows grows to its padded row count, and block rows densify with
        it) and padded up the ``bucket_k`` ladder — becomes the rung's
        compiled tile budget.  Returns (backend, layout-or-None).
        """
        bl = None
        fill = None
        if "bsr" in self._backend_candidates:
            bl = ell_bsr_layout(nbr_staged, self._bsr_block)
            fill = bl.fill
        backend = ops.select_backend(
            self._backend_knob, num_rows=key[0],
            sharded=self.mesh is not None, block_fill=fill,
            use_env=False)  # the hint was pinned at construction
        self._backend_modes[key] = backend
        if backend == "bsr":
            grow = key[0] / max(1, n_valid)
            cap = min(key[0] // self._bsr_block,
                      key[1] * self._bsr_block)  # ≤ BS rows × K edges each
            self._slot_budgets[key] = min(
                bucket_k(int(np.ceil(bl.num_slots * grow))), max(cap, 1))
            logger.info(
                "stream backend: rung %s -> bsr (block fill %.2f, slot "
                "budget %d)", key, fill, self._slot_budgets[key])
        else:
            logger.info("stream backend: rung %s -> %s", key, backend)
        return backend, bl

    # ------------------------------------------------------------------ #
    def _slot_overflow(self, key: tuple[int, int], needed: int) -> None:
        """Record a bsr tile-budget overflow (warned once per rung)."""
        if key not in self._slot_overflow_warned:
            self._slot_overflow_warned.add(key)
            logger.warning(
                "stream bsr: rung %s needs %d tile slots but the compiled "
                "budget is %d — falling back to %s for this batch "
                "(warned once per rung)", key, needed,
                self._slot_budgets[key], self._bsr_twin(key))
        self.backend_overflows += 1

    def _bsr_twin(self, key: tuple[int, int]) -> str:
        """The backend a bsr rung's overflow batches run on: what the
        registry resolves for the rung with no block-fill measurement
        (which keeps bsr out of the scan) and no landmark state."""
        return ops.select_backend("auto", num_rows=key[0],
                                  sharded=self.mesh is not None,
                                  use_env=False)

    # ------------------------------------------------------------------ #
    def _note_touched(self, effect) -> None:
        """Stamp the vertices a Δ_t touched with the current batch index
        (the hot working set is everything stamped within ``hot_ttl``)."""
        g = self.graph
        if len(self._touched_at) < g.num_nodes:
            grown = np.full(g.num_nodes, -1, np.int64)
            grown[: len(self._touched_at)] = self._touched_at
            self._touched_at = grown
        self._touched_at[effect.affected] = self.batches
        self._touched_at[effect.new_ids] = self.batches

    # ------------------------------------------------------------------ #
    def _landmark_gate(self) -> np.ndarray | None:
        """Decide whether this Δ_t streams the hot/cold split; returns
        the hot row mask (or None for plain exact staging).

        The decision must precede the snapshot build (the restriction
        changes the bucket the batch lands in), so it cannot ride the
        per-rung resolution the exact backends use: the registry is
        consulted with the FULL unlabeled count and the landmark state's
        readiness, and the first "landmark" verdict latches for the
        engine's lifetime — every later batch stays on the hot/cold
        contract even when deletions shrink the graph back under the
        auto threshold (per-rung backend modes stay consistent that way).
        """
        g = self.graph
        lm = self._lm
        store = getattr(self.ingestor, "store", None)
        if not lm.ready:
            lm.refresh(g, store)  # lazy activation; cheap no-op early on
        if not self._lm_streaming:
            n_unl = int((g.alive & (g.labels == UNLABELED)).sum())
            resolved = ops.select_backend(
                self._backend_knob, num_rows=bucket(n_unl),
                sharded=self.mesh is not None,
                landmark_ready=lm.ready, use_env=False)
            if resolved != "landmark" or not lm.ready:
                return None
            self._lm_streaming = True
            logger.info(
                "stream landmark: hot/cold split active (%d landmarks, "
                "hot_ttl %d, %d unlabeled rows)", lm.num_landmarks,
                lm.cfg.hot_ttl, n_unl)
        age = self.batches - self._touched_at
        return (self._touched_at >= 0) & (age <= lm.cfg.hot_ttl)

    # ------------------------------------------------------------------ #
    def _landmark_commit(self, p: "_Pending") -> None:
        """Commit-boundary landmark work for a hot/cold batch: refresh
        the factorization incrementally (new rows get assignments; the
        landmark label vector is re-read in O(L)) and fold the low-rank
        estimates over the batch's cold unlabeled rows — rows with no
        assignment (no valid landmark yet) keep their committed labels."""
        g = self.graph
        lm = self._lm
        lm.refresh(g, getattr(self.ingestor, "store", None))
        est, wsum = lm.cold_values(lm.landmark_values(g))
        ids = p.cold_ids
        sel = ids[wsum[ids] > 0]
        live = sel if p.relabelled is None else sel[
            ~np.isin(sel, p.relabelled)]  # later relabels win (see drain)
        g.f[live] = est[live]
        p.view_f[sel] = est[sel]
        self.landmark_batches += 1
        self.landmark_cold_rows += len(sel)

    # ------------------------------------------------------------------ #
    def _stage_single(self, host: HostSnapshot) -> _Staging:
        """Resolve a mesh-less Δ_t: rung backend via the registry; bsr
        rungs component-reorder the rows (Step-1 clustering) and derive
        the per-edge tile-slot map, falling back to the rung's twin
        (``_bsr_twin``) when a batch's slot requirement overflows the
        rung's compiled budget."""
        key = host.bucket_key
        backend = self._backend_modes.get(key)
        order = bl = staged = inv = None
        if backend is None:
            if "bsr" in self._backend_candidates:
                order = component_order(host.nbr)
                staged, inv = reorder_host_snapshot(host, order)
                backend, bl = self._resolve_rung_backend(
                    key, staged.nbr, len(host.unl_ids))
            else:
                backend, bl = self._resolve_rung_backend(
                    key, host.nbr, len(host.unl_ids))
        if backend != "bsr":
            return _Staging(staged=host, backend=backend, transport="single")
        if order is None:
            order = component_order(host.nbr)
            staged, inv = reorder_host_snapshot(host, order)
        if bl is None:
            bl = ell_bsr_layout(staged.nbr, self._bsr_block)
        if bl.num_slots > self._slot_budgets[key]:
            self._slot_overflow(key, bl.num_slots)
            return _Staging(staged=host, backend=self._bsr_twin(key),
                            transport="single")
        self.bsr_batches += 1
        return _Staging(staged=staged, backend="bsr", transport="single",
                        rows=inv[: len(host.unl_ids)], perm=order,
                        slot=bl.slot, num_slots=self._slot_budgets[key])

    # ------------------------------------------------------------------ #
    def _stage_mesh(self, host: HostSnapshot) -> _Staging:
        """Resolve a mesh Δ_t: rung backend + transport mode + plan.

        The rung's backend, transport mode and budgets are decided once,
        at rung entry: ``"auto"`` partitions the first snapshot that
        lands in the rung and takes halo iff the budgeted export fraction
        is at most ``AUTO_EXPORT_FRACTION`` (``"auto:measured"`` times
        one real sweep per transport instead; a single-device mesh always
        takes all-gather).  Within a halo rung the export *layout* is
        re-derived from every batch's topology (the budget tolerates
        stale/extra prefix rows — they ship committed labels); a batch
        whose export counts overflow the budget runs on the rung's
        all-gather twin instead (warned once per rung).  A bsr rung
        stages in the halo row layout under BOTH transports — the tile
        layout is then identical in both programs, which is what makes
        bsr labels bit-identical across transports — and a batch whose
        tile-slot requirement overflows the rung's compiled budget runs
        on the rung's twin (``_bsr_twin``) under the same transport
        routing (warned once per rung; every exact backend is itself
        bit-identical across transports, so the cross-transport contract
        survives fallback).
        """
        key = host.bucket_key
        n_dev = self.mesh.devices.size
        backend = self._backend_modes.get(key)
        mode = self._transport_modes.get(key)
        allgather_only = (self.transport == "allgather"
                          or (self.transport in ("auto", "auto:measured")
                              and n_dev == 1))
        bsr_possible = (backend == "bsr" or (
            backend is None and "bsr" in self._backend_candidates))
        # the halo layout doubles as the bsr row order, so derive it
        # whenever the rung needs halo bytes OR bsr tiles
        need_layout = (bsr_possible or mode == "halo"
                       or (mode is None and not allgather_only))
        layout = (partition.build_halo_plan(host.nbr, n_dev)
                  if need_layout else None)
        bl = None
        if backend is None:
            backend, bl = self._resolve_rung_backend(
                key, layout.nbr if layout is not None else host.nbr,
                len(host.unl_ids))
        if mode is None:
            # need_layout guarantees a layout whenever this branch can
            # pick halo, so only the allgather-only case lacks one
            if allgather_only:
                mode = "allgather"
            else:
                budget = partition.export_budget(layout, len(host.unl_ids))
                if self.transport == "auto:measured":
                    mode = self._measured_mode(key)
                    if mode is None:
                        mode = self._measure_rung_transport(
                            key, host, layout, budget, backend)
                else:
                    frac = budget * n_dev / key[0]
                    mode = ("halo" if self.transport == "halo"
                            or frac <= AUTO_EXPORT_FRACTION else "allgather")
                    if mode == "allgather":
                        logger.info(
                            "stream transport: rung %s export fraction "
                            "%.2f > %.2f — auto takes all-gather", key,
                            frac, AUTO_EXPORT_FRACTION)
                if mode == "halo":
                    self._export_budgets[key] = budget
            self._transport_modes[key] = mode

        # ---- per-Δ_t staging: permute when halo bytes or bsr tiles need
        # the export-prefix row layout ----
        staged, rows, perm = host, None, None
        if backend == "bsr" or mode == "halo":
            if layout is None:
                layout = partition.build_halo_plan(host.nbr, n_dev)
            staged = apply_halo_layout(host, layout)
            rows = layout.inv_perm[: len(host.unl_ids)]
            perm = layout.perm
        slot, num_slots = None, 0
        backend_this = backend
        if backend == "bsr":
            if bl is None:
                bl = ell_bsr_layout(staged.nbr, self._bsr_block)
            if bl.num_slots > self._slot_budgets[key]:
                # slot-budget overflow: this Δ_t rides the rung's twin
                # but keeps the rung's TRANSPORT routing below, so halo
                # accounting (halo_batches + overflows) stays exact
                self._slot_overflow(key, bl.num_slots)
                backend_this = self._bsr_twin(key)
            else:
                slot, num_slots = bl.slot, self._slot_budgets[key]
                self.bsr_batches += 1

        if mode == "halo":
            budget = self._export_budgets[key]
            if int(layout.export_counts.max()) > budget:
                # overflow: this Δ_t's cross-shard rows exceed the rung's
                # compiled export prefix — correctness falls back to the
                # all-gather twin for this batch only
                if key not in self._overflow_warned:
                    self._overflow_warned.add(key)
                    logger.warning(
                        "stream halo: rung %s export count %d overflows "
                        "the compiled budget %d — falling back to "
                        "all-gather for this batch (warned once per rung)",
                        key, int(layout.export_counts.max()), budget)
                self.transport_overflows += 1
            else:
                self.halo_batches += 1
                return _Staging(
                    staged=staged, backend=backend_this, transport="halo",
                    plan=self._halo_plan_for(key, budget, backend_this,
                                             num_slots),
                    rows=rows, perm=perm, slot=slot, num_slots=num_slots)
        return _Staging(
            staged=staged, backend=backend_this, transport="allgather",
            plan=self._plan_for(key, backend_this, num_slots),
            rows=rows, perm=perm, slot=slot, num_slots=num_slots)

    # ------------------------------------------------------------------ #
    def _measured_mode(self, key) -> str | None:
        """Consult the persisted ``auto:measured`` probe cache: a restored
        engine re-entering a rung it (or a predecessor process) already
        timed picks the winner from the cached per-transport sweep times
        instead of paying two probe compiles + timed sweeps again
        (docs/persistence.md §Probe cache).  Returns None on a miss."""
        cached = self._measured.get(key)
        if cached is None:
            return None
        mode = "halo" if cached["halo"] <= cached["allgather"] else "allgather"
        self.probe_cache_hits += 1
        logger.info(
            "stream transport: rung %s probe-cache hit (halo %.2f ms vs "
            "all-gather %.2f ms cached) — taking %s without re-probing",
            key, cached["halo"], cached["allgather"], mode)
        return mode

    # ------------------------------------------------------------------ #
    def _measure_rung_transport(self, key, host, layout, budget,
                                backend) -> str:
        """``auto:measured``: time one real sweep per transport on the
        rung's first snapshot and cache the winner.

        Costs two probe runners (``max_iters=1``, compiled once per rung
        and counted by ``compile_cache_size``) plus two timed sweeps each
        — the price of capturing reconstruct-overhead effects the
        byte-count heuristic cannot see.  The probes never touch the
        engine's donated buffers (``donate=False``, throwaway staging).
        """
        m = key[0] // self.mesh.devices.size
        if budget >= m:
            return "allgather"  # halo ships no fewer bytes: skip the probe
        staged = apply_halo_layout(host, layout)
        slot = None
        bsr_kw = {}
        if backend == "bsr":
            bl = ell_bsr_layout(staged.nbr, self._bsr_block)
            slot = bl.slot
            bsr_kw = dict(block_size=self._bsr_block,
                          num_slots=self._slot_budgets[key])
        times = {}
        for tr in ("allgather", "halo"):
            build = (distributed.build_stream_plan if tr == "allgather"
                     else functools.partial(distributed.build_stream_halo_plan,
                                            export_max=budget))
            plan = build(self.mesh, key, backend=backend, delta=self.delta,
                         max_iters=1, block_rows=self.block_rows,
                         interpret=self.interpret, donate=False, **bsr_kw)
            problem = plan.put_problem(staged.nbr, staged.wgt, staged.wl0,
                                       staged.wl1, staged.valid)
            f0 = plan.put_row(np.full(key[0], 0.5, np.float32))
            fr = plan.put_row(staged.valid)
            kw = ({"slot": plan.put_row2(slot)} if slot is not None else {})
            jax.block_until_ready(plan(problem, f0, fr, **kw).f)  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(plan(problem, f0, fr, **kw).f)
            times[tr] = time.perf_counter() - t0
        mode = "halo" if times["halo"] <= times["allgather"] else "allgather"
        self._measured[key] = {t: round(v * 1e3, 4) for t, v in times.items()}
        logger.info(
            "stream transport: rung %s measured halo %.2f ms vs all-gather "
            "%.2f ms per sweep — taking %s", key, times["halo"] * 1e3,
            times["allgather"] * 1e3, mode)
        return mode

    # ------------------------------------------------------------------ #
    def _commit(
        self, host: HostSnapshot,
        plan: distributed.StreamShardPlan | None = None,
    ) -> PropagationProblem:
        """Stage a host snapshot into the persistent device buffers."""
        key = host.bucket_key
        self.trace.add("engine.h2d_bytes", sum(
            a.nbytes for a in (host.nbr, host.wgt, host.wl0, host.wl1,
                               host.valid)))
        if plan is not None:  # mesh mode: row-sharded staging
            new = plan.put_problem(host.nbr, host.wgt, host.wl0, host.wl1,
                                   host.valid)
        else:
            new = PropagationProblem(
                nbr=jnp.asarray(host.nbr),
                wgt=jnp.asarray(host.wgt),
                wl0=jnp.asarray(host.wl0),
                wl1=jnp.asarray(host.wl1),
                valid=jnp.asarray(host.valid),
            )
        slots = self._buffers.setdefault(key, [None, None])
        gen = self._gen.get(key, 1) ^ 1
        self._gen[key] = gen
        if slots[gen] is not None and ops.on_tpu():
            # ``slots[gen]`` last served batch t-2, whose solve has been
            # drained — safe to donate its storage to this snapshot so the
            # device arena stays flat across the stream.  Donation is a
            # no-op on CPU, where the extra copy would be pure overhead,
            # so there we simply swap the slot and drop the old arrays.
            new = _adopt(slots[gen], new)
        slots[gen] = new
        self.bucket_keys.add(key)
        return new

    # ------------------------------------------------------------------ #
    def submit(self, batch: BatchUpdate) -> StreamStats | None:
        """Apply Δ_t, stage it, launch its solve; returns the now-complete
        stats of the PREVIOUS batch (None on the first call).

        Spans (``self.trace``, each tagged ``batch=`` with the commit id
        this Δ_t will get): ``engine.submit`` around the whole call, and
        its children ``engine.submit.apply`` / ``.build`` / ``.stage`` /
        ``.supernode`` / ``.dispatch`` and ``engine.drain`` of the
        previous batch; ``engine.h2d_bytes`` counts the host arrays
        handed to the device."""
        b = self.batches + 1
        with self.trace.span("engine.submit", batch=b):
            return self._submit(batch, b)

    def _submit(self, batch: BatchUpdate, b: int) -> StreamStats | None:
        t0 = time.perf_counter()
        g = self.graph
        span = self.trace.span

        with span("engine.submit.apply", batch=b):
            # ---- Step 0: arrival ordering (ids are assigned in row
            # order, so this must run before apply_batch) ----
            if self.ingest_order == "locality" and len(batch.ins_emb) > 2:
                from repro.data.synth import cosine_locality_order
                order = cosine_locality_order(
                    np.asarray(batch.ins_emb, np.float32))
                batch = dataclasses.replace(
                    batch, ins_emb=np.asarray(batch.ins_emb)[order],
                    ins_labels=np.asarray(batch.ins_labels)[order])

            # ---- Step 1: change adjustment & sparsification (host) ----
            effect = g.apply_batch(batch, tau=self.tau,
                                   selector=self.ingestor)
            m = len(effect.new_ids)
            if self._pending is not None and batch.rel_ids is not None \
                    and len(batch.rel_ids):
                rel = np.asarray(batch.rel_ids, np.int64)
                rel = rel[(rel >= 0) & (rel < g.num_nodes)]
                rel = rel[g.alive[rel]]  # the relabels apply_batch applied
                p = self._pending
                p.relabelled = (rel if p.relabelled is None
                                else np.union1d(p.relabelled, rel))
            if self._lm is not None:
                self._note_touched(effect)

        # ``effect.affected`` is already alive-filtered, so the frontier
        # below is nonempty iff some affected vertex is unlabeled — an
        # O(|affected|) test, decided BEFORE the O(U·K) snapshot build.
        if not (len(effect.affected)
                and (g.labels[effect.affected] == UNLABELED).any()):
            # No-op Δ_t (empty batch, or deletions touching nothing
            # unlabeled): the solve would run zero sweeps and return f0
            # bit-identically, so skip the snapshot build, device staging
            # and dispatch entirely.  The batch still commits — drain()
            # publishes a LabelView reflecting any alive/labels changes.
            prev = self.drain()
            with span("engine.submit.dispatch", batch=b):
                self.batches += 1
                unl_ids = np.flatnonzero(g.alive & (g.labels == UNLABELED))
                self._pending = _Pending(
                    res=None, unl_ids=unl_ids, t0=t0,
                    num_components=0, frontier_size=0,
                    bucket=(0, 0),  # nothing staged this Δ_t
                    recompiled=False, transport="none", backend="none",
                    view_labels=g.labels.copy(), view_alive=g.alive.copy(),
                    view_f=g.f.copy(), dispatched_at=time.perf_counter(),
                )
            return prev

        with span("engine.submit.build", batch=b):
            # ---- landmark hot/cold gate: decided BEFORE the snapshot
            # build (the hot restriction changes the bucket this Δ_t
            # lands in) ----
            hot = self._landmark_gate() if self._lm is not None else None
            cold_ids = None
            if hot is not None:
                cold_ids = np.flatnonzero(g.alive & (g.labels == UNLABELED)
                                          & ~hot)

            # ---- stage batch-t topology while batch t-1 still
            # propagates ----
            host = build_host_problem(g, max_degree=self.max_degree,
                                      auto_bucket=True,
                                      row_multiple=self._row_multiple,
                                      max_k=self.max_k,
                                      warned=self._max_k_warned,
                                      hot=hot)
            if hot is not None:
                # the hot/cold contract overrides the rung's registry
                # scan — a hot problem is small by design, so per-rung
                # auto would pick an exact backend and mislabel
                # approximate batches
                self._backend_modes[host.bucket_key] = "landmark"
            u = len(host.unl_ids)
            u_pad = len(host.valid)
            frontier = np.zeros(u_pad, bool)
            aff_rows = host.remap[effect.affected]
            frontier[aff_rows[aff_rows >= 0]] = True

        with span("engine.submit.stage", batch=b):
            # resolve this batch's backend/transport/plan through the
            # per-rung registry state; bsr and halo batches permute the
            # snapshot (into component order or the export-prefix layout)
            # before staging — row order is invisible to the fixpoint, so
            # labels stay bit-equal.  ``host`` itself stays in original
            # row order for the supernode init and f0 builds below, which
            # fold back via ``st.rows``.
            st = (self._stage_mesh(host) if self.mesh is not None
                  else self._stage_single(host))
            plan = st.plan
            problem = self._commit(st.staged, plan)
            frontier_staged = (frontier if st.perm is None
                               else frontier[st.perm])
            self.trace.add("engine.h2d_bytes", frontier_staged.nbytes)
            frontier_dev = (plan.put_row(frontier_staged) if plan is not None
                            else jnp.asarray(frontier_staged))

        with span("engine.submit.supernode", batch=b):
            # ---- Step 2: supernode label initialization (host wl0/wl1) --
            n_components = 0
            new_unl = effect.new_ids[g.labels[effect.new_ids] == UNLABELED]
            if m and len(new_unl):
                comp_local = gprime_components(effect, m)
                local_idx = new_unl - effect.new_ids[0]
                comp = compact_labels(jnp.asarray(comp_local))[local_idx]
                n_components = int(jnp.max(comp) + 1) if len(local_idx) else 0
                rows = host.remap[new_unl]
                wl0, wl1 = host.wl0[rows], host.wl1[rows]
                self.trace.add("engine.h2d_bytes", wl0.nbytes + wl1.nbytes)
                f_init = supernode_init(
                    comp, jnp.asarray(wl0), jnp.asarray(wl1),
                    num_segments=max(m, 1))
                g.f[new_unl] = np.asarray(f_init)

        # ---- drain batch t-1 (first moment its result is truly needed:
        # f0 below reads the propagated labels) ----
        prev = self.drain()

        with span("engine.submit.dispatch", batch=b):
            # ---- Step 3: launch this batch's solve (async) ----
            f0 = np.full(u_pad, 0.5, np.float32)
            f0[:u] = g.f[host.unl_ids]
            if st.perm is not None:
                f0 = f0[st.perm]
            # f0 is donated into the solve in both modes; in mesh mode it
            # is staged row-sharded first so each device recycles its own
            # block.
            f0_dev = plan.put_row(f0) if plan is not None else jnp.asarray(f0)
            self.trace.add("engine.h2d_bytes", f0.nbytes)
            slot_dev = None
            if st.slot is not None:
                self.trace.add("engine.h2d_bytes", st.slot.nbytes)
                slot_dev = (plan.put_row2(st.slot) if plan is not None
                            else jnp.asarray(st.slot))
            before = ops.compile_cache_size()
            res = ops.run_propagation(
                problem, f0_dev, frontier_dev,
                delta=self.delta, max_iters=self.max_iters,
                backend=st.backend, block_rows=self.block_rows,
                interpret=self.interpret, donate=True, shard_plan=plan,
                slot=slot_dev, num_slots=st.num_slots or None,
                block_size=self._bsr_block if st.backend == "bsr" else None,
            )
            recompiled = ops.compile_cache_size() > before
            self.recompile_count += recompiled
            self.batches += 1
            self._pending = _Pending(
                res=res, unl_ids=host.unl_ids, t0=t0,
                num_components=n_components,
                frontier_size=int(frontier.sum()),
                bucket=host.bucket_key, recompiled=recompiled,
                transport=st.transport, backend=st.backend,
                rows=st.rows, cold_ids=cold_ids,
                # Batch-t host state (labels/alive fixed by apply_batch
                # above; f now holds batch t-1's committed labels plus
                # this batch's supernode inits).  drain() folds the
                # solved rows over view_f and publishes the result as the
                # committed LabelView.
                view_labels=g.labels.copy(), view_alive=g.alive.copy(),
                view_f=g.f.copy(), dispatched_at=time.perf_counter(),
            )
        return prev

    # ------------------------------------------------------------------ #
    def drain(self) -> StreamStats | None:
        """Block on the in-flight solve and fold its labels back into the
        host graph; returns its stats (None if nothing is pending).

        Draining COMMITS the batch: the committed ``LabelView`` is
        rebuilt here (solved rows folded over the state captured at
        submit), so ``committed_view()`` readers flip atomically from
        batch t-1's labels to batch t's.  Spans: ``engine.drain``, and
        ``engine.drain.wait`` while the host blocks on the device; the
        interval ``engine.inflight`` runs from the end of the submit that
        dispatched a solve to its commit here."""
        p, self._pending = self._pending, None
        if p is None:
            return None
        with self.trace.span("engine.drain", batch=self.commits + 1):
            return self._drain(p)

    def _drain(self, p: _Pending) -> StreamStats:
        if p.res is None:  # no-op batch: nothing was solved
            iterations, converged, resid = 0, True, 0.0
        else:
            with self.trace.span("engine.drain.wait"):
                f = np.asarray(p.res.f)  # synchronizes
            # halo/bsr batches solved in a permuted row order: gather the
            # original rows back through the layout's inverse permutation
            solved = f[p.rows] if p.rows is not None else f[: len(p.unl_ids)]
            if p.relabelled is None:
                self.graph.f[p.unl_ids] = solved
            else:
                keep = ~np.isin(p.unl_ids, p.relabelled)
                self.graph.f[p.unl_ids[keep]] = solved[keep]
            p.view_f[p.unl_ids] = solved
            iterations = int(p.res.iterations)
            converged = bool(p.res.converged)
            resid = float(p.res.max_residual)
        if p.cold_ids is not None and self._lm is not None:
            self._landmark_commit(p)
        self.commits += 1
        self._view = LabelView(f=p.view_f, labels=p.view_labels,
                               alive=p.view_alive, commit_id=self.commits)
        # Commit handoff without host copies: the view's own frozen
        # arrays feed device_put directly.  Republish eagerly only once
        # a device reader exists — engines that never serve device reads
        # pay nothing per commit.
        if self._device_view is not None:
            self._device_view = publish_device_view(self._view,
                                                    self._read_placement)
        self.last_commit_at = time.perf_counter()
        if p.res is not None:
            self.trace.interval("engine.inflight",
                                self.last_commit_at - p.dispatched_at)
        return StreamStats(
            iterations=iterations,
            converged=converged,
            num_components=p.num_components,
            frontier_size=p.frontier_size,
            num_unlabeled=len(p.unl_ids),
            wall_ms=(self.last_commit_at - p.t0) * 1e3,
            max_residual=resid,
            bucket=p.bucket,
            recompiled=p.recompiled,
            transport=p.transport,
            backend=p.backend,
        )

    # ------------------------------------------------------------------ #
    def poll(self) -> StreamStats | None:
        """Non-blocking ``drain``: commit the in-flight batch only if its
        device solve has already finished; otherwise return None without
        waiting.  The serving layer calls this between requests so commits
        land as soon as the device is done, never stalling the caller."""
        p = self._pending
        if p is None:
            return None
        if p.res is not None and not p.res.f.is_ready():
            return None
        return self.drain()

    def waits_on_solve(self, batch: BatchUpdate) -> bool:
        """Whether ``submit(batch)`` would block on the in-flight solve
        before its own drain: the batch inserts rows and the engine
        ingests on the device, so ``DeviceIngestor.select`` reads its
        candidates back from a device that runs programs in order, behind
        the solve.  Committing first (``drain``) then costs the staging
        nothing and makes the solved batch readable a whole staging
        earlier; ``LPService`` does so at the head of an admit."""
        return (self._pending is not None and len(batch.ins_emb) > 0
                and isinstance(self.ingestor, DeviceIngestor))

    @property
    def in_flight(self) -> bool:
        """True while a submitted batch has not been drained (committed)."""
        return self._pending is not None

    def committed_view(self) -> LabelView:
        """The query-side snapshot of the last COMMITTED batch.

        Safe to read while a later batch is in flight: ``submit`` mutates
        the host graph immediately, but the view only advances at drain
        time, so readers never observe a torn half-applied batch.  Before
        any commit it reflects the graph the engine was built around."""
        return self._view

    def device_view(self) -> DeviceLabelView:
        """The committed snapshot ON DEVICE — query bursts run as one
        jitted gather (``DeviceLabelView.query``) instead of per-call
        host indexing.  Published lazily on first call, then refreshed
        eagerly at every drain; placement (replica device / sharded
        rows) was fixed at construction via ``read_placement``.  Safe to
        call concurrently with a drain: views are immutable and both
        ``_view`` and the cache swap atomically, so a racing reader gets
        either the previous or the new commit, never a torn mix — the
        serving read path relies on this to stay off the write lock."""
        dv = self._device_view
        if dv is None or dv.commit_id != self._view.commit_id:
            dv = publish_device_view(self._view, self._read_placement)
            self._device_view = dv
        return dv

    # ------------------------------------------------------------------ #
    def step(self, batch: BatchUpdate) -> StreamStats:
        """Synchronous Δ_t update — ``DynLP.step`` semantics, amortized
        compile.  Use ``submit``/``drain`` directly to pipeline batches."""
        self.submit(batch)
        return self.drain()

    # ------------------------------------------------------------------ #
    def transport_summary(self) -> dict:
        """JSON-friendly account of the sharded transport AND the per-rung
        backend registry decisions: the requested knobs, each rung's
        mode/backend/budgets, and how many batches actually rode
        halo/bsr vs overflowed back to their fallbacks.  Surfaced by
        ``LPService.stats()`` and the streaming benchmarks."""
        def by_rung(d):
            return {f"{u}x{k}": v for (u, k), v in sorted(d.items())}

        return {
            "requested": self.transport,
            "mesh_devices": (int(self.mesh.devices.size)
                             if self.mesh is not None else 0),
            "rung_modes": by_rung(self._transport_modes),
            "export_budgets": by_rung(self._export_budgets),
            "halo_batches": self.halo_batches,
            "overflows": self.transport_overflows,
            "requested_backend": self.backend or "auto",
            "rung_backends": by_rung(self._backend_modes),
            "slot_budgets": by_rung(self._slot_budgets),
            "bsr_batches": self.bsr_batches,
            "backend_overflows": self.backend_overflows,
            "measured_sweep_ms": by_rung(self._measured),
            "probe_cache_hits": self.probe_cache_hits,
            "landmark": {
                "configured": self._lm is not None,
                "streaming": self._lm_streaming,
                "num_landmarks": self._lm.num_landmarks if self._lm else 0,
                "batches": self.landmark_batches,
                "cold_rows": self.landmark_cold_rows,
                "resamples": self._lm.resamples if self._lm else 0,
            },
        }

    # ------------------------------------------------------------------ #
    def checkpoint(self, directory: str, step: int | None = None) -> str:
        """Write one atomic checkpoint of the full incremental state
        (graph buffers, embedding store, rung metadata, probe cache,
        commit counter) under ``directory``; step defaults to the commit
        counter.  Commit-boundary only: raises while a batch is in
        flight — ``drain()`` first.  See ``core.persistence``."""
        from repro.core import persistence

        return persistence.save_engine(self, directory, step)

    def checkpoint_state(self) -> dict:
        """The flat checkpoint tree (for ``CheckpointManager.save_async``
        off-path writes — the ``LPService`` policy path); same
        commit-boundary contract as ``checkpoint``."""
        from repro.core import persistence

        return persistence.engine_state(self)

    @classmethod
    def restore(cls, directory: str, step: int | None = None,
                **overrides) -> "StreamEngine":
        """Rebuild an engine from the latest (or given) checkpoint,
        elastically re-sharded onto whatever ``mesh=`` is active now;
        other keyword overrides replace the checkpointed engine knobs.
        See ``core.persistence.restore_engine``."""
        from repro.core import persistence

        return persistence.restore_engine(directory, step, **overrides)

    # ------------------------------------------------------------------ #
    def predictions(self, cutoff: float = 0.5) -> tuple[np.ndarray, np.ndarray]:
        """(global ids, binary predictions) for alive unlabeled vertices."""
        g = self.graph
        ids = np.flatnonzero(g.alive & (g.labels == UNLABELED))
        return ids, (g.f[ids] >= cutoff).astype(np.int8)
