"""Continuous batching of plan-path query launches.

SURVEY.md §7 hard part 5: per-launch overhead must amortize over many
queries. The reference's answer is a thread pool
(`search` pool, ThreadPool.java:117-181 — thread-per-shard-request);
the TPU-native answer is **batched launches**: concurrent requests with
the same kernel shape coalesce into one vmapped execution
(ops/plan.py plan_topk_batch) and share a single device round-trip.

Leader/follower protocol (no background threads): the first request to
arrive for a shape becomes the leader; while the leader's launch is in
flight, later arrivals queue; whoever arrives first after the pop leads
the next batch and takes the whole queue with it. Under load the batch
size self-tunes to the launch latency (plus an explicit wait, a
fraction of the measured round-trip, taken only when other requests are
pending) — classic continuous batching; a truly idle query still runs
alone with zero added wait.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.ops.plan import unpack_ids as _unpack_ids

from elasticsearch_tpu.ops import device as device_ops
from elasticsearch_tpu.ops import plan as plan_ops
from elasticsearch_tpu.search.plan import BoundPlan, execute_bound
from elasticsearch_tpu.telemetry import flightrecorder as _flight
from elasticsearch_tpu.telemetry.tracing import host_span

_Q_BUCKETS = (1, 2, 4, 8, 16, 32)


def _q_bucket(n: int) -> int:
    for b in _Q_BUCKETS:
        if n <= b:
            return b
    return _Q_BUCKETS[-1]


# NB coalescing tiers: plans whose per-stream selection widths land in
# the same power-of-FOUR tier share a batch signature and pad to the
# tier width, so slightly-different-NB queries (the common mix) coalesce
# into one launch instead of fragmenting into per-pow2 cohorts. Power of
# four bounds the padding waste at 4x device lanes — and only for the
# smallest plan of the cohort; a pow2 ladder would double the signature
# count for ~zero extra coalescing.
_NB_TIER_FLOOR = 64


def _nb_tier(n: int) -> int:
    t = _NB_TIER_FLOOR
    while t < n:
        t *= 4
    return t


class _Entry:
    __slots__ = ("bp", "event", "result", "error", "profiled", "t_enq",
                 "meta", "t_fr", "tenant", "wclass")

    def __init__(self, bp: BoundPlan, profiled: bool = False,
                 t_enq: int = 0, t_fr: float = 0.0,
                 tenant: Optional[str] = None,
                 wclass: Optional[str] = None):
        self.bp = bp
        # the enqueuing request's ambient tenant: cohort occupancy is
        # charged per SLOT, so a hog filling the batch window is
        # attributable even though the launch itself is shared
        self.tenant = tenant
        # and its ambient workload class, for the same per-slot
        # attribution by request kind
        self.wclass = wclass
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        # per-request device attribution (`profile: true` only): the
        # caller flags its entry at enqueue; _run stamps cohort meta
        # (kernel, cohort width, padding waste, launch/readback) only
        # for flagged entries — the profile-off hot path allocates
        # nothing extra
        self.profiled = profiled
        self.t_enq = t_enq
        # enqueue stamp on the flight recorder's clock (always-on when
        # a recorder is ambient): the cohort's queue-wait provenance
        self.t_fr = t_fr
        self.meta: Optional[Dict[str, object]] = None


class _Cohorts:
    """The leader/follower protocol both batchers share. ``SPANS``
    names its three waits as host spans: the leader's adaptive flush
    window, its wait for a launch slot, and a follower's wait for the
    leader's launch."""

    SPANS = ("", "", "")

    def __init__(self, max_batch: int, max_concurrent: int,
                 adaptive_flush_s: float):
        self.max_batch = max_batch
        self._lock = threading.Lock()
        # Launches used to serialize behind one lock; under a device
        # with a high per-sync latency floor that caps throughput at
        # batch/floor. Syncs OVERLAP
        # across threads, so a bounded semaphore lets several batched
        # launches ride the floor concurrently — the wait in acquire()
        # is still the batching window that grows cohorts under load.
        self._launch_slots = threading.BoundedSemaphore(max_concurrent)
        self._pending: Dict[tuple, list] = {}
        self.launches = 0          # stats: total device launches
        self.batched_queries = 0   # stats: queries served via batches
        # EMA of launch+readback latency: when the device round-trip is
        # slow (tens of ms or more), leaders WAIT a fraction
        # of it before popping the queue so cohorts grow — the classic
        # continuous-batching window, sized from measurement instead of
        # a fixed knob. Fast devices (real local TPU: sub-ms) never wait.
        self._lat_ema = 0.0
        # adaptive flush: even on a fast device, a leader that sees
        # OTHER work pending holds the pop for up to this long so the
        # cohort fills — trading ≤~2 ms of p50 for materially larger
        # batches under load (0 disables)
        self.adaptive_flush_s = float(adaptive_flush_s)
        # optional TenantAccounting sink: one cohort slot per entry
        self.tenants = None
        # optional WorkloadAccounting sink: same per-slot charge keyed
        # by request class
        self.workloads = None

    def _ride(self, sig: tuple, entry, run) -> None:
        """Queue ``entry`` under ``sig`` and return once a launch has
        set its result (raising its error). The first request to
        arrive for a shape leads: it lets the cohort grow, waits for a
        launch slot, takes the whole queue and hands it to ``run`` in
        chunks of ``max_batch``. Non-leader entries are always popped
        by a leader that appended before them, so nothing is
        orphaned."""
        with self._lock:
            q = self._pending.setdefault(sig, [])
            q.append(entry)
            leader = len(q) == 1
        if not leader:
            with host_span(self.SPANS[2]):
                entry.event.wait()
        else:
            self._lead(sig, entry, run)
        if entry.error is not None:
            raise entry.error

    def _lead(self, sig: tuple, entry, run) -> None:
        # The wait engages only when concurrency is actually present
        # (other work pending) and is STAGED: stop as soon as this
        # signature's cohort fills a max batch — when a launch costs
        # seconds, padding a 3-query cohort to the batch shape wastes
        # ~10x device time, so waiting a fraction of the measured
        # round-trip to fill the cohort is strictly cheaper. On a FAST
        # device the adaptive flush window still holds the pop for
        # ≤~2 ms when other work is pending, so loaded traffic
        # coalesces instead of racing out in cohorts of one.
        window = (min(0.75 * self._lat_ema, 1.5)
                  if self._lat_ema > 0.03 else self.adaptive_flush_s)
        if window > 0.0:
            with host_span(self.SPANS[0]):
                deadline = time.monotonic() + window
                step = min(0.02, max(window / 4.0, 0.0005))
                while time.monotonic() < deadline:
                    with self._lock:
                        mine = len(self._pending.get(sig, ()))
                        busy = (mine > 1 or len(self._pending) > 1
                                or any(len(q) > 1
                                       for q in self._pending.values()))
                    if mine >= self.max_batch or not busy:
                        break
                    time.sleep(step)
        with host_span(self.SPANS[1]):
            self._launch_slots.acquire()
        try:
            with self._lock:
                batch = self._pending.pop(sig, [])
            if not batch:
                batch = [entry]
            try:
                for start in range(0, len(batch), self.max_batch):
                    run(batch[start:start + self.max_batch])
            except BaseException as exc:
                for e in batch:
                    if not e.event.is_set():
                        e.error = exc
                        e.event.set()
                raise
        finally:
            self._launch_slots.release()


class PlanBatcher(_Cohorts):
    """Shape-bucketed batcher for fused plan launches.

    Eligible: everything but search_after cursors and ad-hoc dense
    masks — plans whose dense mask is a CACHED composed filter column
    batch too, cohorted by the mask's identity so one [ND] column
    serves the launch. Batches are keyed by (segment identity, stream
    shapes, group-table size, k, combine, mask identity, k1, b) so
    stacked launches are homogeneous; Q pads to a power-of-two bucket
    to bound compile count. Under a slow transport the leader waits a
    fraction of the measured launch latency — only when other requests
    are already pending — so cohorts grow without taxing idle queries.
    """

    SPANS = ("plan.flush_wait", "plan.slot_wait", "plan.follower_wait")

    def __init__(self, max_batch: int = 64, max_concurrent: int = 8,
                 adaptive_flush_s: float = 0.002):
        super().__init__(min(max_batch, _Q_BUCKETS[-1]), max_concurrent,
                         adaptive_flush_s)
        self.batch_hist: Dict[int, int] = {}   # pow2 batch-size counts
        # replica-axis fan-out (opt-in; a MeshSearchBackend wired by the
        # service): cohorts split their query axis over a ("replica",)
        # device mesh — corpus replicated, per-query rows sharded — and
        # the SAME kernel runs partitioned by GSPMD, so per-query
        # results stay byte-identical to the single-device launch
        self.mesh = None
        self.mesh_cohorts = 0     # stats: cohorts launched replica-sharded

    # ------------------------------------------------------------------
    @staticmethod
    def _eligible(bp: BoundPlan, after_score) -> bool:
        # dense plans batch when their mask is the CACHED shared object
        # (one [ND] column serves the cohort); ad-hoc device-column
        # masks run singly
        return (after_score is None and not bp.empty
                and (bp.dense_mask is None or bp.dense_shared))

    @staticmethod
    def _signature(bp: BoundPlan, ctx, k: int, k1: float, b: float) -> tuple:
        # selection widths key by COALESCING TIER, not exact width:
        # plans whose NB landed in different power-of-two buckets (the
        # impact-selected mix) still share a cohort; _run pads every
        # member to the widest member's bucket (zero-block selections
        # with weight 0 are inert in the kernel)
        return (
            ctx.segment.name, ctx.segment.live_version,
            tuple((id(st.block_docids), _nb_tier(int(st.sel_blocks.shape[0])))
                  for st in bp.streams),
            int(bp.group_kind.shape[0]), bp.combine, k,
            id(bp.dense_mask) if bp.dense_mask is not None else None,
            id(bp.script_fn) if bp.script_fn is not None else None,
            round(k1, 6), round(b, 6),
        )

    # ------------------------------------------------------------------
    def execute(self, bp: BoundPlan, ctx, k: int, k1: float, b: float,
                after_score: Optional[float] = None):
        from elasticsearch_tpu.search import profile as _prof
        from elasticsearch_tpu.telemetry import context as _telectx
        profiled = _prof.recording()
        if not self._eligible(bp, after_score):
            return execute_bound(bp, ctx, k, k1, b, after_score)
        sig = self._signature(bp, ctx, k, k1, b)
        fr = _flight.current()
        entry = _Entry(bp, profiled=profiled,
                       t_enq=_prof.now_ns() if profiled else 0,
                       t_fr=fr.clock() if fr is not None else 0.0,
                       tenant=_telectx.current_tenant(),
                       wclass=_telectx.current_workload_class())
        self._ride(sig, entry,
                   lambda chunk: self._run(chunk, ctx, k, k1, b))
        if profiled:
            self._record_attribution(entry)
        return entry.result

    # ------------------------------------------------------------------
    @staticmethod
    def _record_attribution(entry: _Entry) -> None:
        """Fold the cohort meta `_run` stamped on this entry into the
        caller's active profile recorder, adding the batcher wait (time
        between enqueue and the completed launch, minus the launch
        itself — the continuous-batching cost this request paid to ride
        a cohort)."""
        from elasticsearch_tpu.search import profile as _prof
        meta = entry.meta
        if meta is None:
            return
        total_ms = max(0.0, (_prof.now_ns() - entry.t_enq) / 1e6)
        rec = dict(meta)
        rec["batch_wait_ms"] = round(
            max(0.0, total_ms - float(rec.get("launch_ms", 0.0))), 3)
        _prof.record_device(rec)

    # ------------------------------------------------------------------
    @staticmethod
    def _pad1(a: np.ndarray, width: int, fill) -> np.ndarray:
        if a.shape[0] == width:
            return a
        out = np.full(width, fill, a.dtype)
        out[:a.shape[0]] = a
        return out

    def _run(self, batch: List[_Entry], ctx, k: int, k1: float, b: float):
        qn = len(batch)
        bucket = _q_bucket(qn)
        pad = bucket - qn
        bps = [e.bp for e in batch] + [batch[0].bp] * pad

        proto = bps[0]
        streams = []
        ngpad = int(proto.group_kind.shape[0])
        for si, st in enumerate(proto.streams):
            # a tier-coalesced cohort pads every member to the WIDEST
            # member's (power-of-two) selection width: pads select the
            # reserved zero block with weight 0 — all-zero tfs, so the
            # kernel never counts them for presence or score (the
            # bind_plan pad convention)
            width = max(int(bp.streams[si].sel_blocks.shape[0])
                        for bp in bps)
            zero_block = int(st.block_docids.shape[0]) - 1
            # host-side np.stack (µs): selections are numpy; the jit
            # boundary uploads the stacked batch asynchronously
            streams.append(plan_ops.FieldStream(
                st.block_docids, st.block_tfs, st.doc_lens, st.avg_len,
                np.stack([self._pad1(bp.streams[si].sel_blocks, width,
                                     zero_block) for bp in bps]),
                np.stack([self._pad1(bp.streams[si].sel_group, width,
                                     ngpad) for bp in bps]),
                np.stack([self._pad1(bp.streams[si].sel_sub, width, 0)
                          for bp in bps]),
                np.stack([self._pad1(bp.streams[si].sel_weight, width,
                                     0.0) for bp in bps]),
                np.stack([self._pad1(bp.streams[si].sel_const, width,
                                     False) for bp in bps])))
        gk = np.stack([bp.group_kind for bp in bps])
        gr = np.stack([bp.group_req for bp in bps])
        gc = np.stack([bp.group_const for bp in bps])
        nm = np.asarray([bp.n_must for bp in bps], np.int32)
        nf = np.asarray([bp.n_filter for bp in bps], np.int32)
        ms = np.asarray([bp.msm for bp in bps], np.int32)
        bo = np.asarray([bp.bonus for bp in bps], np.float32)
        ti = np.asarray([bp.tie for bp in bps], np.float32)
        live = ctx.live
        rmesh = None
        if (self.mesh is not None and proto.dense_mask is None
                and proto.script_fn is None):
            # replica fan-out: corpus arrays ride as replicated (P())
            # handles, every per-query row shards P("replica") — the
            # identical jitted kernel then partitions over the Q axis
            rmesh = self.mesh.replica_mesh_for(bucket)
        if rmesh is not None:
            mb = self.mesh
            streams = [plan_ops.FieldStream(
                mb.replicated(rmesh, st.block_docids),
                mb.replicated(rmesh, st.block_tfs),
                mb.replicated(rmesh, st.doc_lens),
                mb.replicated(rmesh, st.avg_len),
                mb.shard_rows(rmesh, st.sel_blocks),
                mb.shard_rows(rmesh, st.sel_group),
                mb.shard_rows(rmesh, st.sel_sub),
                mb.shard_rows(rmesh, st.sel_weight),
                mb.shard_rows(rmesh, st.sel_const))
                for st in streams]
            live = mb.replicated(rmesh, ctx.live)
            gk, gr, gc = (mb.shard_rows(rmesh, a) for a in (gk, gr, gc))
            nm, nf, ms, bo, ti = (mb.shard_rows(rmesh, a)
                                  for a in (nm, nf, ms, bo, ti))
        any_prof = any(e.profiled for e in batch)
        t0p = 0
        if any_prof:
            from elasticsearch_tpu.search import profile as _prof
            t0p = _prof.now_ns()
        t0 = time.monotonic()
        # flight provenance: annotate the launch inside plan_topk_batch
        # with the cohort's fill/capacity + the queue wait its OLDEST
        # rider paid (recorder clock — virtual under the deterministic
        # harness), and route the single packed readback through the
        # tracked ops/device funnel
        fr = _flight.current()
        enq = [e.t_fr for e in batch if e.t_fr]
        qw_ns = (int(max(0.0, fr.clock() - min(enq)) * 1e9)
                 if fr is not None and enq else 0)
        with _flight.annotate_launch(qn, bucket, queue_wait_ns=qw_ns):
            packed = plan_ops.plan_topk_batch(
                streams, gk, gr, gc, live, nm, nf, ms, bo, ti,
                k1=k1, b=b, k=k, combine=proto.combine,
                # cohort-shared filter column + script (signature keys
                # on their identities)
                dense_mask=proto.dense_mask, script_fn=proto.script_fn)
        # ONE readback for the whole batch (rows are packed buffers)
        rows = device_ops.readback("search.batching.plan_cohort", packed,
                                   profile=False)
        dt = time.monotonic() - t0
        if dt < 5.0:   # ignore compile-length outliers (first launches)
            self._lat_ema = (dt if self._lat_ema == 0.0
                             else 0.8 * self._lat_ema + 0.2 * dt)
        self.launches += 1
        self.batched_queries += qn
        self.batch_hist[bucket] = self.batch_hist.get(bucket, 0) + 1
        if self.tenants is not None:
            # integer slot counts only — replay-deterministic
            for e in batch:
                self.tenants.record_cohort(e.tenant)
        if self.workloads is not None:
            for e in batch:
                self.workloads.record_cohort(e.wclass)
        if rmesh is not None:
            self.mesh_cohorts += 1
            self.mesh._dispatch("replica", qn)
        if any_prof:
            # cohort meta for `profile: true` device attribution — the
            # launch is timed on the profile clock (virtual under the
            # deterministic harness → replay-identical trees); padding
            # waste is per entry: the padded selection slots the cohort
            # tier forced on THIS plan, plus the Q-bucket pad rows
            launch_ms = round((_prof.now_ns() - t0p) / 1e6, 3)
            widths = [int(st.sel_blocks.shape[1]) for st in streams]
            row_slots = sum(widths)        # one cohort row's padded slots
            readback = int(rows[0].nbytes)
            for e in batch:
                if not e.profiled:
                    continue
                # per-entry waste: the tier-padded slots of THIS plan's
                # row that its own selection did not fill (the Q-bucket
                # pad rows are cohort overhead, visible via q_bucket
                # vs cohort)
                own = sum(int(st.sel_blocks.shape[0])
                          for st in e.bp.streams)
                e.meta = {
                    "kernel": "plan_topk_batch",
                    "cohort": qn,
                    **({"mesh_shape":
                        {"replica": rmesh.devices.size}}
                       if rmesh is not None else {}),
                    "q_bucket": bucket,
                    "nb_bucket": max(widths) if widths else 0,
                    "nb_selected": own,
                    "padding_waste_pct": round(
                        100.0 * (1.0 - own / row_slots), 1)
                    if row_slots else 0.0,
                    "launch_ms": launch_ms,
                    "readback_bytes": readback,
                }
        for i, e in enumerate(batch):
            e.result = plan_ops.unpack_result(rows[i], k)
            e.event.set()

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        return {
            "launches": self.launches,
            "batched_queries": self.batched_queries,
            "avg_batch": (self.batched_queries / self.launches
                          if self.launches else 0.0),
            "batch_hist": {str(kk): v for kk, v
                           in sorted(self.batch_hist.items())},
            "mesh_cohorts": self.mesh_cohorts,
        }


# ---------------------------------------------------------------------------
# kNN branch batching
# ---------------------------------------------------------------------------

_CUT_BUCKETS = (128, 256, 512, 1024, 2048, 4096)


def _cut_bucket(n: int) -> int:
    for b in _CUT_BUCKETS:
        if n <= b:
            return b
    return _CUT_BUCKETS[-1]


class _KnnEntry:
    __slots__ = ("qvec", "cut", "event", "result", "error", "profiled",
                 "t_enq", "meta", "t_fr", "tenant", "wclass", "t_mono")

    def __init__(self, qvec: np.ndarray, cut: int,
                 profiled: bool = False, t_enq: int = 0,
                 t_fr: float = 0.0, tenant: Optional[str] = None,
                 wclass: Optional[str] = None, t_mono: int = 0):
        self.qvec = qvec
        # enqueue time (monotonic ns; 0: not timed) — the start of the
        # query's `knn.queue_wait`
        self.t_mono = t_mono
        self.cut = cut
        self.tenant = tenant
        self.wclass = wclass
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.profiled = profiled
        self.t_enq = t_enq
        self.t_fr = t_fr
        self.meta: Optional[Dict[str, object]] = None


class KnnBatcher(_Cohorts):
    """Continuous batching for kNN branch launches — the vector
    analogue of :class:`PlanBatcher`. Concurrent kNN queries against
    the same device slab coalesce into ONE
    ``ops.vector.knn_nominate_batch`` launch ([Q, D] matmul + batched
    top-k) and share a single packed readback; without this every
    hybrid-RRF request pays its own degraded-mode matvec chain
    (BASELINE config 5's serving cost). Scores and int32 docids pack
    into one float32 buffer (bitcast) so the cohort syncs exactly once.
    """

    SPANS = ("knn.flush_wait", "knn.slot_wait", "knn.follower_wait")

    def __init__(self, max_batch: int = 64, max_concurrent: int = 8,
                 adaptive_flush_s: float = 0.002):
        super().__init__(max_batch, max_concurrent, adaptive_flush_s)
        # optional MetricsRegistry: `knn.queue_wait` (enqueue to the
        # cohort's launch) and `knn.rerank` (the host re-rank), in ms
        self.metrics = None

    def topk(self, dv, live, qvec: np.ndarray, cut: int,
             host_vectors=None) -> Tuple[np.ndarray, np.ndarray]:
        """Top-``cut`` (scores, docids) for one query vector against a
        DeviceVectors slab, honoring the segment's device ``live`` mask
        (deletes). ``host_vectors`` (the segment's f32 host copy)
        enables the exact re-rank when the slab is quantized
        (KnnQuery._exact_rerank parity). The cut caps at the slab's
        padded row count — lax.top_k cannot exceed the axis."""
        from elasticsearch_tpu.search import profile as _prof
        from elasticsearch_tpu.telemetry import context as _telectx
        profiled = _prof.recording()
        nd = int(dv.vectors.shape[0])
        bucket_cut = min(_cut_bucket(cut), nd)
        sig = (id(dv.vectors), id(live), dv.similarity, bucket_cut,
               int(qvec.shape[0]))
        fr = _flight.current()
        entry = _KnnEntry(np.asarray(qvec, np.float32), cut,
                          profiled=profiled,
                          t_enq=_prof.now_ns() if profiled else 0,
                          t_fr=fr.clock() if fr is not None else 0.0,
                          tenant=_telectx.current_tenant(),
                          wclass=_telectx.current_workload_class(),
                          t_mono=time.monotonic_ns())
        self._ride(sig, entry,
                   lambda chunk: self._run(chunk, dv, live, bucket_cut))
        if profiled:
            PlanBatcher._record_attribution(entry)
        t0 = time.monotonic_ns()
        with host_span("knn.rerank"):
            out = self._finish(entry, dv, host_vectors)
        if self.metrics is not None:
            self.metrics.observe("knn.rerank",
                                 (time.monotonic_ns() - t0) / 1e6)
        return out

    # ------------------------------------------------------------------
    def _run(self, batch: List[_KnnEntry], dv, live, cut: int):
        from elasticsearch_tpu.ops import vector as vec_ops
        import jax
        # the cohort's [Qb, ND] float32 score matrix must fit next to
        # the slab (an 8M-doc slab already holds ~11.5 GiB of HBM) —
        # cap Qb so the ephemeral stays ≤ ~1 GiB
        nd = int(dv.vectors.shape[0])
        cap = max(1, (1 << 28) // max(nd, 1))
        allowed = max((b for b in _Q_BUCKETS if b <= cap), default=1)
        for start in range(0, len(batch), allowed):
            chunk = batch[start:start + allowed]
            qn = len(chunk)
            bucket = min(_q_bucket(qn), allowed)
            qs = np.stack([e.qvec for e in chunk]
                          + [chunk[0].qvec] * (bucket - qn))
            any_prof = any(e.profiled for e in chunk)
            t0p = 0
            if any_prof:
                from elasticsearch_tpu.search import profile as _prof
                t0p = _prof.now_ns()
            t0 = time.monotonic()
            if self.metrics is not None:
                waits = self.metrics.histogram("knn.queue_wait")
                t_launch = time.monotonic_ns()
                for e in chunk:
                    if e.t_mono:
                        waits.observe((t_launch - e.t_mono) / 1e6)
            fr = _flight.current()
            enq = [e.t_fr for e in chunk if e.t_fr]
            qw_ns = (int(max(0.0, fr.clock() - min(enq)) * 1e9)
                     if fr is not None and enq else 0)
            with _flight.annotate_launch(qn, bucket,
                                         queue_wait_ns=qw_ns):
                top_s, top_i = vec_ops.knn_nominate_batch(
                    jnp.asarray(qs), dv.vectors, dv.sq_norms,
                    dv.has_value, live, dv.similarity, cut)
            # ONE packed readback: ids as float CASTS (exact < 2^24 —
            # ops/plan.pack_result)
            packed = jnp.concatenate(
                [top_s, top_i.astype(jnp.float32)], axis=1)
            rows = device_ops.readback("search.batching.knn_cohort",
                                       packed, profile=False)
            dt = time.monotonic() - t0
            with self._lock:
                if dt < 5.0:
                    self._lat_ema = (dt if self._lat_ema == 0.0
                                     else 0.8 * self._lat_ema + 0.2 * dt)
                self.launches += 1
                self.batched_queries += qn
            if self.tenants is not None:
                for e in chunk:
                    self.tenants.record_cohort(e.tenant)
            if self.workloads is not None:
                for e in chunk:
                    self.workloads.record_cohort(e.wclass)
            if any_prof:
                launch_ms = round((_prof.now_ns() - t0p) / 1e6, 3)
                for e in chunk:
                    if e.profiled:
                        # same semantics as PlanBatcher: per-row slot
                        # waste — the bucketed cut columns this entry's
                        # own request did not need; Q-pad rows stay
                        # visible via q_bucket vs cohort
                        e.meta = {
                            "kernel": "knn_nominate_batch",
                            "cohort": qn,
                            "q_bucket": bucket,
                            "nb_bucket": cut,
                            "padding_waste_pct": round(
                                100.0 * (1.0 - min(e.cut, cut) / cut),
                                1) if cut else 0.0,
                            "launch_ms": launch_ms,
                            "readback_bytes": int(rows[0].nbytes),
                        }
            for i, e in enumerate(chunk):
                scores = rows[i, :cut].copy()
                ids = _unpack_ids(rows[i, cut:])
                e.result = (scores, ids)
                e.event.set()

    # ------------------------------------------------------------------
    def _finish(self, entry: _KnnEntry, dv,
                host_vectors) -> Tuple[np.ndarray, np.ndarray]:
        scores, ids = entry.result
        ok = np.isfinite(scores)
        scores, ids = scores[ok], ids[ok]
        if dv.vectors.dtype != jnp.float32 and host_vectors is not None:
            # exact f32 re-rank of the nominated candidates
            # (KnnQuery._exact_rerank parity: bf16 only NOMINATES)
            valid = ids < host_vectors.shape[0]
            scores, ids = scores[valid], ids[valid]
            cand = host_vectors[ids].astype(np.float32)
            q32 = entry.qvec.astype(np.float32)
            if dv.similarity == "cosine":
                nrm = (np.linalg.norm(cand, axis=1)
                       * np.linalg.norm(q32))
                raw = cand @ q32 / np.where(nrm > 0, nrm, 1.0)
                scores = (1.0 + raw) / 2.0
            elif dv.similarity == "dot_product":
                scores = (1.0 + cand @ q32) / 2.0
            else:
                d2 = np.sum((cand - q32[None, :]) ** 2, axis=1)
                scores = 1.0 / (1.0 + d2)
        order = np.lexsort((ids, -scores))[: entry.cut]
        return scores[order], ids[order]

    def stats(self) -> Dict[str, float]:
        return {
            "knn_launches": self.launches,
            "knn_batched_queries": self.batched_queries,
            "knn_avg_batch": (self.batched_queries / self.launches
                              if self.launches else 0.0),
        }
