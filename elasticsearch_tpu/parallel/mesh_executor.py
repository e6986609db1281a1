"""MeshExecutor — the serving backend that puts every chip behind `_search`.

Round-5 verdict: the production `_search` path scored shards one device
at a time while the 8-device `shard_map` pipeline existed only as a
dryrun. This module promotes it: a `MeshExecutor` materializes a stacked
device-resident view of an index's LIVE shards (every (shard, segment)
pair is one entry on the ``shards`` mesh axis, folded when there are
more entries than devices) and executes whole same-plan query groups as
ONE SPMD program — per-entry scoring + local top-k on each device, an
`all_gather` + k-way merge over the ICI, `psum` totals — replacing S
sequential kernel dispatches and S host round-trips with one packed
download.

Design contract (float-exactness with the single-device path):

  * entries are (shard, segment) pairs in (shard asc, segment asc)
    order, so per-entry scoring is the SAME computation the sequential
    ChunkedScorer/segment kernels run — same block-aligned tilings
    (ops/wand.get_tiling), same shard-level BM25 weights
    (JaxExecutor._segment_weights via BlockMaxIndex), same
    `w - w/(1 + tf·inv)` accumulation in the same tile order, same
    live-doc masking — and the device merge's (score desc, slot asc)
    order equals the coordinator's (score desc, shard asc, segment asc,
    doc asc) tie-break. Only the merge topology changes.
  * no pruning on the mesh path: totals come out exact (relation "eq"),
    which is the sequential path's behavior whenever its capped-total
    proof does not fire.

Lifecycle: the stacked view is rebuilt LAZILY when any shard's engine
`change_generation` moves (refresh/merge/delete); stale snapshots keep
serving in-flight launches until the references die. Every stacked
upload charges the HBM ledger's ``mesh`` category up front and the
build DEGRADES to the single-device path (`MeshUnavailable`) instead of
tripping the breaker when the budget cannot fit it.

Knobs (common/settings.py): ES_TPU_MESH (auto|force|off),
ES_TPU_MESH_DEVICES, ES_TPU_MESH_DATA, ES_TPU_MESH_T_MAX.
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.settings import (
    batch_buckets,
    bucket_for,
    mesh_data_axis,
    mesh_devices_cap,
    mesh_mode,
    mesh_t_max,
)
from ..index.segment import INVALID_DOC, TILE
from ..ops import scoring
from .mesh import DATA_AXIS, SHARD_AXIS, fold_factor, make_mesh
from .sharded import (
    build_mesh_agg_step,
    build_mesh_ann_step,
    build_mesh_knn_step,
    build_mesh_rerank_step,
    build_mesh_sparse_step,
    build_mesh_text_step,
)

BPAD = scoring.BPAD

# Process-wide SPMD launch lock: two batcher workers enqueueing mesh
# programs concurrently could interleave per-device enqueue order
# (worker A lands first on device 0, worker B first on device 1),
# inverting the collectives' rendezvous order across devices — a
# deadlock on any backend. Holding the lock around the ENQUEUE (the
# jitted step call, which returns before execution completes) keeps
# every device's queue identically ordered; execution and the packed
# downloads still overlap freely.
_LAUNCH_LOCK = threading.Lock()


class MeshUnavailable(Exception):
    """The mesh path cannot serve this group (no devices, HBM budget
    breach, slot overflow, unsupported plan shape). Callers degrade to
    the single-device sequential path — never an error surface.
    ``budget`` marks the HBM-ledger degrade specifically."""

    def __init__(self, msg: str, budget: bool = False):
        super().__init__(msg)
        self.budget = budget


class MeshHit(NamedTuple):
    score: float
    shard: int
    segment: int
    local_doc: int
    doc_id: str


class MeshTopDocs(NamedTuple):
    """One query's globally merged mesh result. `snapshot` pins the
    reader generation the hits were scored against so the fetch phase
    reads the same point-in-time sources."""

    total: int
    relation: str
    max_score: Optional[float]
    hits: List[MeshHit]
    snapshot: "_MeshSnapshot"


class _MeshSnapshot:
    """One generation's stacked device view of the index's shards."""

    def __init__(self, mesh, fold, entries, readers, executors, gens):
        self.mesh = mesh
        self.fold = fold
        self.entries = entries  # [(sid, si)] in (shard, segment) asc order
        self.readers = readers  # sid -> ShardReader
        self.executors = executors  # sid -> JaxExecutor
        self.gens = gens
        g = mesh.shape[SHARD_AXIS]
        self.e_pad = g * fold
        self.n_docs_max = max(
            (readers[sid].segments[si].num_docs for sid, si in entries),
            default=1,
        )
        self.charges: List[Tuple[str, int]] = []
        self.live = None  # bool[E_pad, Nmax] device (live ∧ in-range)
        self.text: Dict[str, dict] = {}  # field -> stacked text arrays
        self.knn: Dict[str, dict] = {}  # field -> stacked vector arrays
        self.aggs: Dict[tuple, dict] = {}  # stacked agg column views
        self.steps: Dict[tuple, object] = {}
        self.closed = False
        # ---- incremental rebuild state: per-entry identity keys
        # ((sid, shard generation, si) — a bumped shard invalidates ALL
        # its entries, since inverse norms / idf weights are shard-level
        # stats) and the host staging copies of every stacked view.
        # When the next generation rebuilds, rows whose key is unchanged
        # copy from the previous stack instead of re-extracting (and
        # re-downloading) tilings — a one-shard NRT refresh rebuilds
        # only that shard's rows. ----
        gen_of = dict(gens)
        self.entry_keys = [
            (sid, gen_of.get(sid), si) for sid, si in entries
        ]
        self.host_stacks: Dict[object, dict] = {}
        # (prev entry_keys, prev host_stacks) captured at build — plain
        # data, NOT a reference to the previous snapshot, so the old
        # generation's device arrays die on schedule
        self.reuse_src: Optional[tuple] = None

    @property
    def device_ids(self) -> Tuple[int, ...]:
        return tuple(
            getattr(d, "id", i)
            for i, d in enumerate(self.mesh.devices.ravel())
        )

    def charge(self, nbytes: int) -> None:
        from ..common.memory import hbm_ledger

        if not hbm_ledger.would_fit(nbytes):
            hbm_ledger.note_degraded()
            raise MeshUnavailable(
                f"mesh stack of {nbytes} bytes exceeds the HBM budget",
                budget=True,
            )
        hbm_ledger.add("mesh", nbytes, breaker=False)
        self.charges.append(("mesh", nbytes))

    def release(self) -> None:
        from ..common.memory import hbm_ledger

        self.closed = True
        charges, self.charges = self.charges, []
        for cat, nbytes in charges:
            hbm_ledger.release(cat, nbytes)


class MeshAggPlan:
    """A compiled mesh agg body — the batcher's ``mesh_agg`` job plan.
    ``sig`` groups structurally identical dashboard shapes into one
    SPMD launch; the query (match plan terms / match_all) varies per
    row. ``terms``/``boost``/``msm`` delegate to the match plan so the
    mesh text packers can treat agg jobs like match jobs."""

    def __init__(self, nodes, specs, mplan):
        self.nodes = nodes
        self.specs = specs
        self.mplan = mplan  # batcher MatchPlan | None (match_all)
        self.sig = (tuple(specs), mplan is not None)

    @property
    def terms(self):
        return self.mplan.terms if self.mplan is not None else ()

    @property
    def boost(self) -> float:
        return self.mplan.boost if self.mplan is not None else 1.0

    @property
    def msm(self) -> int:
        return self.mplan.msm if self.mplan is not None else 1


class MeshExecutor:
    """Mesh-parallel serving engine of ONE index (owned by IndexService).

    The QueryBatcher routes same-plan query groups here (job kinds
    ``mesh_match`` / ``mesh_serve`` / ``mesh_knn``): `dispatch_*`
    launches the SPMD step asynchronously, `collect_*` performs the one
    packed download and finishes the waiters — the same dispatch/collect
    split (and pipeline depth) as the single-device families.
    """

    # the counters of the node's `pipeline.mesh` block, at zero (summed
    # over the node's mesh executors; a node with none reports these)
    NODE_STATS = {"pipeline.mesh": {
        "routed": 0,  # requests served start-to-finish by the mesh
        "launches": 0,  # SPMD programs dispatched
        "jobs": 0,  # queries carried by those programs
        "rebuilds": 0,  # snapshot rebuilds on generation bumps
        "degraded": 0,  # HBM-budget degrades to single-device
        "fallbacks": 0,  # routed requests that fell back mid-flight
    }}

    def __init__(self, service):
        self.service = service
        self._lock = threading.RLock()
        self._snapshot: Optional[_MeshSnapshot] = None
        self.stats = {
            **self.NODE_STATS["pipeline.mesh"],
            "incremental_rebuilds": 0,  # rebuilds that reused prev rows
            "entries_reused": 0,  # stacked rows copied, not re-extracted
        }

    # ---- routing predicate ----

    def available(self) -> bool:
        mode = mesh_mode()
        if mode == "off":
            return False
        svc = self.service
        if svc.routing is not None and not self._all_shards_local():
            # distributed mode: the stack can only serve when every
            # shard has a queryable copy on this node
            return False
        if str(svc.settings.get("search.backend")) != "jax":
            return False
        if mode == "force":
            return True
        try:
            n_dev = len(self._devices())
        except Exception:  # pragma: no cover - no jax backend
            return False
        return n_dev >= 2 and svc.num_shards >= 2

    def _all_shards_local(self) -> bool:
        """Distributed-mode gate: every shard of the index must have a
        QUERYABLE copy here — an installed engine whose node is the
        primary or an in-sync replica. A relocation-driven routing
        change that adds/removes local engines bumps the `_gens()` key
        (engine set changes), so the next ensure_snapshot rebuilds
        incrementally while in-flight launches keep serving off their
        pinned snapshot reference."""
        svc = self.service
        if svc.local_node is None:
            return True
        for sid in range(svc.num_shards):
            if sid not in svc._local:
                return False
            e = svc._entry(sid) or {}
            if (e.get("primary") != svc.local_node
                    and svc.local_node not in (e.get("in_sync") or [])):
                return False
        return True

    def _devices(self):
        devs = list(jax.devices())
        cap = mesh_devices_cap()
        return devs[:cap] if cap else devs

    @property
    def device_ids(self) -> Tuple[int, ...]:
        snap = self._snapshot
        if snap is not None and not snap.closed:
            return snap.device_ids
        return tuple(
            getattr(d, "id", i) for i, d in enumerate(self._devices())
        )

    # ---- snapshot lifecycle ----

    def _gens(self) -> tuple:
        svc = self.service
        try:
            return tuple(
                (sid, svc.local_shard(sid).change_generation)
                for sid in range(svc.num_shards)
            )
        except KeyError as e:
            # a shard relocated away between available() and here: the
            # caller degrades to the per-shard path for this request
            raise MeshUnavailable(str(e))

    def fresh(self) -> bool:
        snap = self._snapshot
        return snap is not None and not snap.closed and snap.gens == self._gens()

    def ensure_snapshot(self) -> _MeshSnapshot:
        gens = self._gens()
        snap = self._snapshot
        if snap is not None and not snap.closed and snap.gens == gens:
            return snap
        with self._lock:
            snap = self._snapshot
            gens = self._gens()
            if snap is not None and not snap.closed and snap.gens == gens:
                return snap
            new = self._build_snapshot(gens)
            old, self._snapshot = self._snapshot, new
            if old is not None:
                self.stats["rebuilds"] += 1
                # in-flight launches hold their own snapshot reference;
                # the ledger charge is released now, the arrays die with
                # the last reference (same contract as executor close)
                old.release()
            return new

    def _build_snapshot(self, gens) -> _MeshSnapshot:
        svc = self.service
        readers = {}
        executors = {}
        entries = []
        for sid in range(svc.num_shards):
            try:
                shard = svc.local_shard(sid)
            except KeyError as e:
                raise MeshUnavailable(str(e))
            ex = svc._executor(shard)
            from ..search.executor import NumpyExecutor

            if isinstance(ex, NumpyExecutor):
                raise MeshUnavailable("numpy backend shard")
            executors[sid] = ex
            readers[sid] = ex.reader
            for si, seg in enumerate(ex.reader.segments):
                if seg.num_docs > 0:
                    entries.append((sid, si))
        if not entries:
            raise MeshUnavailable("index has no live segments")
        devices = self._devices()
        if not devices:
            raise MeshUnavailable("no devices")
        n_data = mesh_data_axis()
        if BPAD % n_data or n_data > len(devices):
            n_data = 1
        mesh = make_mesh(len(entries), n_data=n_data, devices=devices)
        fold = fold_factor(mesh, len(entries))
        snap = _MeshSnapshot(mesh, fold, entries, readers, executors, gens)
        # incremental rebuild: adopt the PREVIOUS snapshot's host
        # staging stacks (plain arrays, not the snapshot itself) so
        # views can copy unchanged-entry rows instead of re-extracting —
        # a one-shard NRT refresh re-stages only that shard's rows
        old = self._snapshot
        if old is not None and not old.closed and old.host_stacks:
            snap.reuse_src = (old.entry_keys, old.host_stacks)
        # live ∧ in-range mask, shared by every family
        live = np.zeros((snap.e_pad, snap.n_docs_max), bool)

        def _fill_live(e: int) -> None:
            sid, si = snap.entries[e]
            n = readers[sid].segments[si].num_docs
            l = readers[sid].live_docs[si]
            live[e, :n] = True if l is None else l

        self._fill_stack(snap, "live", {"live": live}, _fill_live)
        snap.charge(live.nbytes)
        snap.live = jax.device_put(
            live, NamedSharding(mesh, P(SHARD_AXIS, None))
        )
        return snap

    def _fill_stack(self, snap, key, arrays, fill_entry) -> int:
        """Fills the leading-entry-axis rows of a stacked host view:
        entries whose (sid, shard-generation, si) key is unchanged from
        the previous snapshot copy their previous row (same envelope
        shape required); everything else re-extracts via `fill_entry`.
        Registers the stack for the NEXT rebuild and returns the reused
        row count."""
        prev_map = None
        prev_arrays = None
        if snap.reuse_src is not None:
            prev_keys, prev_stacks = snap.reuse_src
            got = prev_stacks.get(key)
            # ROW-shape compatibility only: appending a segment changes
            # the entry padding (leading axis) but unchanged shards'
            # rows still copy over as long as the per-row envelope
            # (t_max / n_docs_max / dims) is stable
            if got is not None and set(got) >= set(arrays) and all(
                got[name].shape[1:] == arr.shape[1:]
                and got[name].dtype == arr.dtype
                for name, arr in arrays.items()
            ):
                prev_arrays = got
                prev_map = {k: i for i, k in enumerate(prev_keys)}
        reused = 0
        for e in range(len(snap.entries)):
            pi = (
                prev_map.get(snap.entry_keys[e])
                if prev_map is not None
                else None
            )
            if pi is not None:
                for name, arr in arrays.items():
                    arr[e] = prev_arrays[name][pi]
                reused += 1
            else:
                fill_entry(e)
        if reused:
            self.stats["entries_reused"] += reused
            if not getattr(snap, "_counted_incremental", False):
                snap._counted_incremental = True
                self.stats["incremental_rebuilds"] += 1
        snap.host_stacks[key] = arrays
        return reused

    def close(self) -> None:
        with self._lock:
            snap, self._snapshot = self._snapshot, None
            if snap is not None:
                snap.release()

    # ---- stacked field views (lazy, per snapshot) ----

    def _text_view(self, snap: _MeshSnapshot, field: str) -> dict:
        view = snap.text.get(field)
        if view is not None:
            return view
        with self._lock:
            view = snap.text.get(field)
            if view is not None:
                return view
            bmxs = []
            tilings = []
            t_max = 1
            for sid, si in snap.entries:
                bmx = snap.executors[sid].block_index(si, field)
                bmxs.append(bmx)
                tilings.append(None if bmx is None else bmx.tiling)
                if bmx is not None:
                    t_max = max(t_max, int(bmx.tiling.doc_ids.shape[0]))
            doc_ids = np.full(
                (snap.e_pad, t_max, TILE), INVALID_DOC, np.int32
            )
            tfs = np.zeros((snap.e_pad, t_max, TILE), np.int32)
            inv = np.zeros((snap.e_pad, snap.n_docs_max), np.float32)

            def _fill_text(e: int) -> None:
                sid, si = snap.entries[e]
                tiling = tilings[e]
                if tiling is not None:
                    nt = int(tiling.doc_ids.shape[0])
                    doc_ids[e, :nt] = np.asarray(tiling.doc_ids)
                    tfs[e, :nt] = np.asarray(tiling.tfs)
                n = snap.readers[sid].segments[si].num_docs
                ex = snap.executors[sid]
                inv[e, :n] = np.asarray(ex._inv_norm(si, field, n))

            # unchanged-shard rows copy from the previous generation's
            # staging stack (no tiling download, no norm re-extract)
            self._fill_stack(
                snap,
                ("text", field),
                {"doc_ids": doc_ids, "tfs": tfs, "inv": inv},
                _fill_text,
            )
            nbytes = doc_ids.nbytes + tfs.nbytes + inv.nbytes
            snap.charge(nbytes)
            sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "doc_ids": jax.device_put(doc_ids, sh3),
                "tfs": jax.device_put(tfs, sh3),
                "inv_norm": jax.device_put(inv, sh2),
                "bmxs": bmxs,
            }
            snap.text[field] = view
            return view

    def _knn_view(self, snap: _MeshSnapshot, field: str) -> dict:
        view = snap.knn.get(field)
        if view is not None:
            return view
        with self._lock:
            view = snap.knn.get(field)
            if view is not None:
                return view
            mats = []
            for sid, si in snap.entries:
                vf = snap.readers[sid].segments[si].vectors.get(field)
                if vf is None:
                    mats.append(None)
                    continue
                mat = (
                    vf.unit_vectors
                    if vf.similarity == "cosine" and vf.unit_vectors is not None
                    else vf.vectors
                )
                mats.append((mat, vf))
            present = [m for m in mats if m is not None]
            if not present:
                raise MeshUnavailable(f"no entry has vector field [{field}]")
            dims = int(present[0][0].shape[1])
            similarity = present[0][1].similarity
            dtype = np.result_type(*[m[0].dtype for m in present])
            if np.issubdtype(dtype, np.integer):
                dtype = np.float32  # a byte field: the mesh step squares rows
            vectors = np.zeros((snap.e_pad, snap.n_docs_max, dims), dtype)
            cand = np.zeros((snap.e_pad, snap.n_docs_max), bool)
            n_per_entry = np.zeros(snap.e_pad, np.int64)
            live_stack = snap.host_stacks.get("live")
            live_host = (
                live_stack["live"]
                if live_stack is not None
                else np.asarray(jax.device_get(snap.live))
            )
            for e, (sid, si) in enumerate(snap.entries):
                got = mats[e]
                if got is None:
                    continue
                mat, vf = got
                if int(mat.shape[1]) != dims or vf.similarity != similarity:
                    raise MeshUnavailable(
                        f"vector field [{field}] has mixed dims/similarity"
                    )
                n_per_entry[e] = snap.readers[sid].segments[si].num_docs

            def _fill_knn(e: int) -> None:
                got = mats[e]
                if got is None:
                    return
                mat, vf = got
                n = int(n_per_entry[e])
                vectors[e, :n] = mat
                cand[e, :n] = vf.exists & live_host[e, :n]

            self._fill_stack(
                snap,
                ("knn", field, dims, similarity),
                {"vectors": vectors, "cand": cand},
                _fill_knn,
            )
            snap.charge(vectors.nbytes + cand.nbytes)
            sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "vectors": jax.device_put(vectors, sh3),
                "cand": jax.device_put(cand, sh2),
                "dims": dims,
                "similarity": similarity,
                "n_per_entry": n_per_entry,
            }
            snap.knn[field] = view
            return view

    def _sparse_view(
        self, snap: _MeshSnapshot, field: str, quantized: bool
    ) -> dict:
        """Stacked impact-ordered postings for one `sparse_vector`
        field: each entry's tile planes padded to the widest tile count,
        plus the per-entry SparseField handles (each entry has its OWN
        term dictionary / tile layout / dequant scales, so plan packing
        resolves per entry). Only the serving column for this
        `quantized` mode is stacked — the other column never rides the
        ICI."""
        key = ("sparse", field, bool(quantized))
        view = snap.text.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.text.get(key)
            if view is not None:
                return view
            sfs = []
            t_max = 1
            for sid, si in snap.entries:
                sf = (
                    getattr(snap.readers[sid].segments[si], "sparse", None)
                    or {}
                ).get(field)
                sfs.append(sf)
                if sf is not None:
                    t_max = max(t_max, int(sf.n_tiles))
            if all(sf is None for sf in sfs):
                raise MeshUnavailable(
                    f"no entry has sparse_vector field [{field}]"
                )
            vdtype = np.int8 if quantized else np.float32
            doc_ids = np.full(
                (snap.e_pad, t_max, TILE), INVALID_DOC, np.int32
            )
            values = np.zeros((snap.e_pad, t_max, TILE), vdtype)

            def _fill_sparse(e: int) -> None:
                sf = sfs[e]
                if sf is None:
                    return
                nt = int(sf.n_tiles)
                doc_ids[e, :nt] = np.asarray(sf.doc_ids)
                values[e, :nt] = np.asarray(
                    sf.qweights if quantized else sf.weights
                )

            self._fill_stack(
                snap,
                key,
                {"doc_ids": doc_ids, "values": values},
                _fill_sparse,
            )
            snap.charge(doc_ids.nbytes + values.nbytes)
            sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
            view = {
                "doc_ids": jax.device_put(doc_ids, sh3),
                "values": jax.device_put(values, sh3),
                "sfs": sfs,
            }
            snap.text[key] = view
            return view

    def _ann_view(self, snap: _MeshSnapshot, field: str, spec) -> dict:
        """Stacked IVF view: per-entry centroids (replicated scan),
        cluster-major permuted blocks + CSR bounds (clusters stay
        sharded with their entries). Reuses each entry's OWNING
        executor's IvfSegmentIndex, so the mesh path probes the exact
        same centroids/permutation as the per-shard path — parity by
        construction. Any entry without an index (small-segment floor,
        HBM degrade) raises MeshUnavailable and the per-shard
        coordinator serves the request with its own exact floor."""
        key = ("ann", field, spec)
        view = snap.knn.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.knn.get(key)
            if view is not None:
                return view
            idxs = []
            for sid, si in snap.entries:
                idx = snap.executors[sid].ann_index(si, field, spec)
                if idx is None:
                    raise MeshUnavailable(
                        f"entry [{sid}][{si}] has no IVF index for "
                        f"[{field}] (exact floor / HBM degrade)"
                    )
                idxs.append(idx)
            dims = idxs[0].dims
            similarity = idxs[0].similarity
            for idx in idxs:
                if idx.dims != dims or idx.similarity != similarity:
                    raise MeshUnavailable(
                        f"vector field [{field}] has mixed dims/similarity"
                    )
            quant = bool(spec.quantized) and all(
                i.host_qvecs_flat is not None for i in idxs
            )
            e_pad = snap.e_pad
            nlist_max = max(i.nlist for i in idxs)
            fmax = max(i.host_perm.shape[0] for i in idxs)
            cmax = max(i.cmax for i in idxs)
            cents = np.zeros((e_pad, nlist_max, dims), np.float32)
            cvalid = np.zeros((e_pad, nlist_max), bool)
            starts = np.zeros((e_pad, nlist_max), np.int32)
            counts = np.zeros((e_pad, nlist_max), np.int32)
            perm = np.zeros((e_pad, fmax), np.int32)
            if quant:
                vecs = np.zeros((e_pad, fmax, dims), np.int8)
                scales = np.zeros((e_pad, fmax), np.float32)
            else:
                vdt = np.result_type(
                    *[i.host_vecs_flat.dtype for i in idxs]
                )
                vecs = np.zeros((e_pad, fmax, dims), vdt)
                scales = None
            v2 = (
                np.zeros((e_pad, fmax), np.float32)
                if similarity == "l2_norm"
                else None
            )
            cand = np.zeros((e_pad, fmax), bool)
            n_per_entry = np.zeros(e_pad, np.int64)
            live_host = np.asarray(jax.device_get(snap.live))
            for e, ((sid, si), idx) in enumerate(zip(snap.entries, idxs)):
                vf = snap.readers[sid].segments[si].vectors[field]
                n = snap.readers[sid].segments[si].num_docs
                nl = idx.nlist
                F = idx.host_perm.shape[0]
                cents[e, :nl] = idx.host_centroids
                cvalid[e, :nl] = True
                starts[e, :nl] = idx.host_starts
                counts[e, :nl] = idx.host_counts
                perm[e, :F] = idx.host_perm
                if quant:
                    vecs[e, :F] = idx.host_qvecs_flat
                    scales[e, :F] = idx.host_scales_flat
                else:
                    vecs[e, :F] = idx.host_vecs_flat
                if v2 is not None:
                    hv = idx.host_vecs_flat.astype(np.float32)
                    v2[e, :F] = (hv * hv).sum(axis=1)
                base = vf.exists & live_host[e, :n]
                # candidate mask permuted into flat slot order (pad
                # slots stay False; the rank<count test masks them too)
                cand[e, : idx.n] = base[idx.host_perm[: idx.n]]
                n_per_entry[e] = n
            nbytes = (
                cents.nbytes + cvalid.nbytes + starts.nbytes
                + counts.nbytes + perm.nbytes + vecs.nbytes
                + cand.nbytes
                + (scales.nbytes if scales is not None else 0)
                + (v2.nbytes if v2 is not None else 0)
            )
            snap.charge(nbytes)
            sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "centroids": jax.device_put(cents, sh3),
                "cvalid": jax.device_put(cvalid, sh2),
                "starts": jax.device_put(starts, sh2),
                "counts": jax.device_put(counts, sh2),
                "perm": jax.device_put(perm, sh2),
                "vecs": jax.device_put(vecs, sh3),
                "scales": (
                    jax.device_put(scales, sh2) if scales is not None
                    else None
                ),
                "v2": jax.device_put(v2, sh2) if v2 is not None else None,
                "cand": jax.device_put(cand, sh2),
                "dims": dims,
                "similarity": similarity,
                "cmax": cmax,
                "nlists": [i.nlist for i in idxs],
                "n_per_entry": n_per_entry,
            }
            snap.knn[key] = view
            return view

    def _ann_step(self, snap, field, spec, kc):
        key = ("ann_step", field, spec, kc)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    view = self._ann_view(snap, field, spec)
                    step = build_mesh_ann_step(
                        snap.mesh,
                        view["centroids"],
                        view["cvalid"],
                        view["starts"],
                        view["counts"],
                        view["perm"],
                        view["vecs"],
                        view["scales"],
                        view["v2"],
                        view["cand"],
                        view["similarity"],
                        spec.nprobe,
                        kc,
                        view["cmax"],
                    )
                    snap.steps[key] = step
        return step

    # ---- stacked aggregation views (lazy, per snapshot) ----

    def _agg_num_view(self, snap: _MeshSnapshot, field: str) -> dict:
        """Stacked float32 doc-value column (min/max), exact int32 copy
        (sums), and exists mask [E, Nmax]."""
        from ..search import aggs_device

        key = ("num", field)
        view = snap.aggs.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.aggs.get(key)
            if view is not None:
                return view
            vals = np.zeros((snap.e_pad, snap.n_docs_max), np.float32)
            ivals = np.zeros((snap.e_pad, snap.n_docs_max), np.int32)
            exists = np.zeros((snap.e_pad, snap.n_docs_max), bool)
            for e, (sid, si) in enumerate(snap.entries):
                nf = snap.readers[sid].segments[si].numerics.get(field)
                if nf is None:
                    continue
                n = len(nf.values)
                vals[e, :n] = nf.values.astype(np.float32)
                exists[e, :n] = nf.exists
                p = aggs_device.col_profile(snap.executors[sid], si, field)
                if p.sum_exact and p.n_exist:
                    col = np.zeros(n, np.int32)
                    col[nf.exists] = (
                        nf.values[nf.exists].astype(np.int64).astype(
                            np.int32
                        )
                    )
                    ivals[e, :n] = col
            snap.charge(vals.nbytes + ivals.nbytes + exists.nbytes)
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "values": jax.device_put(vals, sh2),
                "ivalues": jax.device_put(ivals, sh2),
                "exists": jax.device_put(exists, sh2),
            }
            snap.aggs[key] = view
            return view

    def _agg_ord_view(self, snap: _MeshSnapshot, field: str) -> dict:
        """GLOBAL ordinal table + stacked per-entry multi-value CSR
        mapped onto it: the ordinal-table union across the ``shards``
        axis happens here at snapshot build, so the device step only
        scatter-adds per-entry count vectors and ``psum``s them."""
        key = ("ord", field)
        view = snap.aggs.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.aggs.get(key)
            if view is not None:
                return view
            per_entry = []
            vocab = set()
            l_max = 1
            for sid, si in snap.entries:
                of = snap.readers[sid].segments[si].ordinals.get(field)
                per_entry.append(of)
                if of is not None:
                    vocab.update(of.ord_terms)
                    l_max = max(l_max, len(of.mv_ords))
            gterms = sorted(vocab)
            gmap = {t: i for i, t in enumerate(gterms)}
            gords = np.zeros((snap.e_pad, l_max), np.int32)
            edocs = np.zeros((snap.e_pad, l_max), np.int32)
            evalid = np.zeros((snap.e_pad, l_max), bool)
            for e, of in enumerate(per_entry):
                if of is None or not len(of.mv_ords):
                    continue
                L = len(of.mv_ords)
                remap = np.array(
                    [gmap[t] for t in of.ord_terms], np.int32
                )
                gords[e, :L] = remap[of.mv_ords]
                edocs[e, :L] = np.repeat(
                    np.arange(len(of.mv_offsets) - 1, dtype=np.int32),
                    np.diff(of.mv_offsets),
                )
                evalid[e, :L] = True
            snap.charge(gords.nbytes + edocs.nbytes + evalid.nbytes)
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "gterms": gterms,
                "gords": jax.device_put(gords, sh2),
                "edocs": jax.device_put(edocs, sh2),
                "evalid": jax.device_put(evalid, sh2),
            }
            snap.aggs[key] = view
            return view

    def _agg_histo_view(
        self, snap: _MeshSnapshot, field: str, interval: int, offset: int
    ) -> dict:
        """Stacked GLOBAL-relative histogram bucket ids (host int64
        floor-division, exact at any span) + exists [E, Nmax]."""
        key = ("histo", field, int(interval), int(offset))
        view = snap.aggs.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.aggs.get(key)
            if view is not None:
                return view
            qs = []
            for sid, si in snap.entries:
                nf = snap.readers[sid].segments[si].numerics.get(field)
                if nf is None or not nf.exists.any():
                    qs.append(None)
                    continue
                qs.append(
                    (nf.values[nf.exists].astype(np.int64) - offset)
                    // interval
                )
            qmins = [int(q.min()) for q in qs if q is not None]
            if not qmins:
                raise MeshUnavailable(f"no entry has field [{field}]")
            qmin = min(qmins)
            nb = max(int(q.max()) for q in qs if q is not None) - qmin + 1
            from ..search.aggs_device import MAX_DEVICE_BUCKETS

            if nb > MAX_DEVICE_BUCKETS:
                raise MeshUnavailable(f"histogram would make {nb} buckets")
            ids = np.zeros((snap.e_pad, snap.n_docs_max), np.int32)
            exists = np.zeros((snap.e_pad, snap.n_docs_max), bool)
            for e, ((sid, si), q) in enumerate(zip(snap.entries, qs)):
                if q is None:
                    continue
                nf = snap.readers[sid].segments[si].numerics.get(field)
                n = len(nf.values)
                col = np.zeros(n, np.int32)
                col[nf.exists] = (q - qmin).astype(np.int32)
                ids[e, :n] = col
                exists[e, :n] = nf.exists
            snap.charge(ids.nbytes + exists.nbytes)
            sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
            view = {
                "qmin": qmin,
                "nb": nb,
                "nbpad": scoring.next_bucket(nb, 16),
                "ids": jax.device_put(ids, sh2),
                "exists": jax.device_put(exists, sh2),
            }
            snap.aggs[key] = view
            return view

    # ---- compiled step cache ----

    def _text_step(self, snap, fields, kb, t_shapes, with_cnt,
                   count_signed, combine, tie):
        key = ("text", fields, kb, t_shapes, with_cnt, count_signed,
               combine, tie)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    views = [self._text_view(snap, f) for f in fields]
                    step = build_mesh_text_step(
                        snap.mesh,
                        [v["doc_ids"] for v in views],
                        [v["tfs"] for v in views],
                        [v["inv_norm"] for v in views],
                        snap.live,
                        kb,
                        with_cnt=with_cnt,
                        count_signed=count_signed,
                        combine=combine,
                        tie=tie,
                    )
                    snap.steps[key] = step
        return step

    def _rerank_view(self, snap: _MeshSnapshot, model) -> dict:
        """Stacked `rank_vectors` view for one RerankModel: per-entry
        CSR bounds over LOCAL doc ids plus each entry's flat token
        block (tail-padded with `tmax` zero rows, the ops/ivf gather
        trick), int8 + per-token scales for quantized models. Entries
        without the field read as zero-token docs (maxsim 0) — exactly
        the per-shard column's semantics."""
        key = ("rerank", model)
        view = snap.text.get(key)
        if view is not None:
            return view
        with self._lock:
            view = snap.text.get(key)
            if view is not None:
                return view
            from ..models import rerank as rerank_model

            n_max = snap.n_docs_max
            tmax = 1
            flat_max = 1
            mvfs = []
            for sid, si in snap.entries:
                mvf = snap.readers[sid].segments[si].multi_vectors.get(
                    model.field
                )
                mvfs.append(mvf)
                if mvf is not None and len(mvf.tok_vectors):
                    tmax = max(tmax, mvf.max_tokens)
                    flat_max = max(flat_max, int(len(mvf.tok_vectors)))
            dims = int(model.dims) or next(
                (
                    int(m.tok_vectors.shape[1])
                    for m in mvfs
                    if m is not None and len(m.tok_vectors)
                ),
                1,
            )
            fmax = flat_max + tmax
            starts = np.zeros((snap.e_pad, n_max), np.int32)
            counts = np.zeros((snap.e_pad, n_max), np.int32)
            toks = np.zeros((snap.e_pad, fmax, dims), np.float32)
            for e, mvf in enumerate(mvfs):
                if mvf is None or not len(mvf.tok_vectors):
                    continue
                n = len(mvf.tok_offsets) - 1
                offs = mvf.tok_offsets.astype(np.int64)
                starts[e, :n] = offs[:-1]
                counts[e, :n] = np.diff(offs)
                toks[e, : len(mvf.tok_vectors)] = mvf.tok_vectors
            scales_dev = None
            if model.quantized:
                flat = toks.reshape(-1, dims)
                qv, scales = rerank_model.quantize_tokens(flat)
                toks_q = qv.reshape(snap.e_pad, fmax, dims)
                scales = scales.reshape(snap.e_pad, fmax)
                nbytes = (
                    starts.nbytes + counts.nbytes + toks_q.nbytes
                    + scales.nbytes
                )
                snap.charge(nbytes)
                sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
                sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
                toks_dev = jax.device_put(toks_q, sh3)
                scales_dev = jax.device_put(scales, sh2)
            else:
                nbytes = starts.nbytes + counts.nbytes + toks.nbytes
                snap.charge(nbytes)
                sh3 = NamedSharding(snap.mesh, P(SHARD_AXIS, None, None))
                sh2 = NamedSharding(snap.mesh, P(SHARD_AXIS, None))
                toks_dev = jax.device_put(toks, sh3)
            view = {
                "starts": jax.device_put(
                    starts, NamedSharding(snap.mesh, P(SHARD_AXIS, None))
                ),
                "counts": jax.device_put(
                    counts, NamedSharding(snap.mesh, P(SHARD_AXIS, None))
                ),
                "toks": toks_dev,
                "scales": scales_dev,
                "tmax": int(tmax),
                "dims": dims,
            }
            snap.text[key] = view
            return view

    def _rerank_step(self, snap, field, kb, t_shape, with_cnt, model,
                     k_req, window, qb):
        key = ("rerank", field, model, kb, t_shape, with_cnt, k_req,
               window, qb)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    view = self._text_view(snap, field)
                    rview = self._rerank_view(snap, model)
                    step = build_mesh_rerank_step(
                        snap.mesh,
                        view["doc_ids"],
                        view["tfs"],
                        view["inv_norm"],
                        snap.live,
                        rview["starts"],
                        rview["counts"],
                        rview["toks"],
                        rview["scales"],
                        kb,
                        k_req,
                        window,
                        rview["tmax"],
                        with_cnt=with_cnt,
                    )
                    snap.steps[key] = step
        return step

    def _knn_step(self, snap, field, kc):
        key = ("knn", field, kc)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    view = self._knn_view(snap, field)
                    step = build_mesh_knn_step(
                        snap.mesh,
                        view["vectors"],
                        view["cand"],
                        view["similarity"],
                        kc,
                    )
                    snap.steps[key] = step
        return step

    def _sparse_step(self, snap, field, quantized, kb, t_shape):
        key = ("sparse", field, bool(quantized), kb, t_shape)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    view = self._sparse_view(snap, field, quantized)
                    step = build_mesh_sparse_step(
                        snap.mesh,
                        view["doc_ids"],
                        view["values"],
                        snap.live,
                        kb,
                    )
                    snap.steps[key] = step
        return step

    # ---- plan packing (host side; mirrors the sequential builders) ----

    def _rows_for(self, snap, n_jobs: int) -> int:
        """The SPMD launch's query-row bucket: the same pad-bucket
        ladder as the single-device batcher, constrained to a multiple
        of the mesh ``data`` axis (the query batch is sharded along it)
        so routing a single query through the mesh doesn't reintroduce
        the full BPAD-row floor."""
        n_data = int(snap.mesh.shape.get(DATA_AXIS, 1))
        return min(
            bucket_for(n_jobs, batch_buckets(BPAD), multiple_of=n_data),
            max(BPAD, n_data),
        )

    def _pack_match(self, snap, view, jobs, t_cap, rows: int):
        """Per-(entry, job) tile plans in EXACTLY the order of the
        batcher's `_dispatch_match_group`: BlockMaxIndex.plan term
        order, all tiles essential (no pruning on the mesh path)."""
        e_pad = snap.e_pad
        lists: List[List[Tuple[np.ndarray, np.ndarray]]] = []
        t_max = 1
        slots = 0
        for e in range(len(snap.entries)):
            bmx = view["bmxs"][e]
            row = []
            for j in jobs:
                if bmx is None:
                    row.append((None, None))
                    continue
                plans = bmx.plan(list(j.plan.terms), j.plan.boost)
                tl = [
                    np.arange(
                        p.tile_start, p.tile_start + p.tile_count,
                        dtype=np.int64,
                    )
                    for p in plans
                ]
                wl = [
                    np.full(p.tile_count, p.weight, np.float32)
                    for p in plans
                ]
                ti = np.concatenate(tl) if tl else np.empty(0, np.int64)
                tw = np.concatenate(wl) if wl else np.empty(0, np.float32)
                if len(ti) > t_cap:
                    raise MeshUnavailable(
                        f"match plan overflows mesh tile cap [{t_cap}]"
                    )
                t_max = max(t_max, len(ti))
                slots += len(ti)
                row.append((ti, tw))
            lists.append(row)
        T = scoring.next_bucket(t_max)
        ti_a = np.zeros((e_pad, rows, T), np.int32)
        tw_a = np.zeros((e_pad, rows, T), np.float32)
        tv_a = np.zeros((e_pad, rows, T), bool)
        for e, row in enumerate(lists):
            for ji, (ti, tw) in enumerate(row):
                if ti is None or not len(ti):
                    continue
                ti_a[e, ji, : len(ti)] = ti
                tw_a[e, ji, : len(ti)] = tw
                tv_a[e, ji, : len(ti)] = True
        return ti_a, tw_a, tv_a, T, slots

    def _pack_serve_field(self, snap, view, jobs, field, t_cap, rows: int):
        """One field's signed-weight tile plans (the MultiFusedScorer
        weight-sign convention via JaxExecutor.fused_plan_field's float
        path: w = weights[tid] * boost * term_boost, negated when the
        term only scores)."""
        e_pad = snap.e_pad
        lists = []
        t_max = 1
        slots = 0
        for e in range(len(snap.entries)):
            bmx = view["bmxs"][e]
            row = []
            for j in jobs:
                group = next(
                    g for g in j.plan.groups if g.field == field
                )
                if bmx is None:
                    row.append((None, None))
                    continue
                tiling = bmx.tiling
                tl: List[np.ndarray] = []
                wl: List[np.ndarray] = []
                for t, tb, counted in group.terms:
                    tid = bmx._term_index.get(t)
                    if tid is None or not int(tiling.term_tile_count[tid]):
                        continue
                    w = float(bmx.weights[tid]) * j.plan.boost * tb
                    if w < 0.0:
                        raise MeshUnavailable("negative term weight")
                    if w == 0.0:
                        w = 1e-30
                    if not counted:
                        w = -w
                    s0 = int(tiling.term_tile_start[tid])
                    c = int(tiling.term_tile_count[tid])
                    tl.append(np.arange(s0, s0 + c, dtype=np.int64))
                    wl.append(np.full(c, w, np.float32))
                ti = np.concatenate(tl) if tl else np.empty(0, np.int64)
                tw = np.concatenate(wl) if wl else np.empty(0, np.float32)
                if len(ti) > t_cap:
                    raise MeshUnavailable(
                        f"serve plan overflows mesh tile cap [{t_cap}]"
                    )
                t_max = max(t_max, len(ti))
                slots += len(ti)
                row.append((ti, tw))
            lists.append(row)
        T = scoring.next_bucket(t_max)
        ti_a = np.zeros((e_pad, rows, T), np.int32)
        tw_a = np.zeros((e_pad, rows, T), np.float32)
        tv_a = np.zeros((e_pad, rows, T), bool)
        for e, row in enumerate(lists):
            for ji, (ti, tw) in enumerate(row):
                if ti is None or not len(ti):
                    continue
                ti_a[e, ji, : len(ti)] = ti
                tw_a[e, ji, : len(ti)] = tw
                tv_a[e, ji, : len(ti)] = True
        return ti_a, tw_a, tv_a, T, slots

    def _pack_sparse(self, snap, view, jobs, quantized, t_cap, rows: int):
        """Per-(entry, job) impact-tile plans in EXACTLY the sequential
        _dispatch_sparse_group order: ops/impact.impact_tile_lists term
        order with each entry's dequant scales folded on host, every
        tile essential (no pruning on the mesh path)."""
        from ..ops import impact as impact_ops

        e_pad = snap.e_pad
        lists: List[List[Tuple[Optional[np.ndarray], Optional[np.ndarray]]]] = []
        t_max = 1
        slots = 0
        for e in range(len(snap.entries)):
            sf = view["sfs"][e]
            row = []
            for j in jobs:
                if sf is None or not sf.n_tiles:
                    row.append((None, None))
                    continue
                _tids, tws, _bws, starts, counts = impact_ops.impact_tile_lists(
                    sf, j.plan.terms, j.plan.weights, quantized
                )
                tl = [
                    np.arange(s0, s0 + c, dtype=np.int64)
                    for s0, c in zip(starts, counts)
                ]
                wl = [
                    np.full(int(c), w, np.float32)
                    for c, w in zip(counts, tws)
                ]
                ti = np.concatenate(tl) if tl else np.empty(0, np.int64)
                tw = np.concatenate(wl) if wl else np.empty(0, np.float32)
                if len(ti) > t_cap:
                    raise MeshUnavailable(
                        f"sparse plan overflows mesh tile cap [{t_cap}]"
                    )
                t_max = max(t_max, len(ti))
                slots += len(ti)
                row.append((ti, tw))
            lists.append(row)
        T = scoring.next_bucket(t_max)
        ti_a = np.zeros((e_pad, rows, T), np.int32)
        tw_a = np.zeros((e_pad, rows, T), np.float32)
        tv_a = np.zeros((e_pad, rows, T), bool)
        for e, row in enumerate(lists):
            for ji, (ti, tw) in enumerate(row):
                if ti is None or not len(ti):
                    continue
                ti_a[e, ji, : len(ti)] = ti
                tw_a[e, ji, : len(ti)] = tw
                tv_a[e, ji, : len(ti)] = True
        return ti_a, tw_a, tv_a, T, slots

    # ---- dispatch / collect (batcher worker entry points) ----

    def dispatch_match(self, jobs, kb: int):
        snap = self.ensure_snapshot()
        field = jobs[0].plan.field
        view = self._text_view(snap, field)
        rows = self._rows_for(snap, len(jobs))
        ti, tw, tv, T, slots = self._pack_match(
            snap, view, jobs, mesh_t_max(), rows
        )
        msm = np.ones(rows, np.int32)
        msm[: len(jobs)] = [j.plan.msm for j in jobs]
        with_cnt = any(j.plan.msm > 1 for j in jobs)
        rescore = getattr(jobs[0].plan, "rescore", None)
        if rescore is not None:
            return self._dispatch_match_rescore(
                snap, jobs, field, kb, rows, ti, tw, tv, msm, with_cnt,
                slots, rescore,
            )
        step = self._text_step(
            snap, (field,), kb, (T,), with_cnt, False, "sum", 0.0
        )
        with _LAUNCH_LOCK:
            out = step((ti,), (tw,), (tv,), msm)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        flops = scoring.text_plan_flops(slots, 0, 0)
        return {"snap": snap, "out": out, "flops": flops, "rows": rows}

    def _dispatch_match_rescore(self, snap, jobs, field, kb, rows,
                                ti, tw, tv, msm, with_cnt, slots,
                                rescore):
        """The fused first-stage + rerank SPMD launch: each entry
        rescores its own local top-k BEFORE the all_gather, so the ICI
        carries already-reranked candidates. Routing precondition: one
        live segment per shard — that makes the per-entry window
        identical to the per-shard path's post-merge window, so the
        two paths agree bit-for-bit."""
        from ..common.faults import faults as _faults
        from ..models import rerank as rerank_model
        from ..ops import rerank as rerank_ops

        model, spec = rescore
        _faults.check("rerank.score", field=model.field, mesh=1)
        sids = [sid for sid, _si in snap.entries]
        if len(set(sids)) != len(sids):
            raise MeshUnavailable(
                "mesh rescore needs one live segment per shard"
            )
        rview = self._rerank_view(snap, model)
        k_req = int(jobs[0].k)
        window = min(int(spec.window_size), k_req)
        qv = rerank_model.prepare_query_vectors(
            spec.query_vectors, model.dims, model.similarity
        )
        qb = max(4, scoring.next_bucket(max(len(qv), 1), 4))
        qtoks = np.zeros((rows, qb, rview["dims"]), np.float32)
        qvalid = np.zeros((rows, qb), bool)
        qtoks[:, : len(qv)] = qv[None, :, :]
        qvalid[:, : len(qv)] = True
        weights = np.asarray(
            [spec.query_weight, spec.rescore_query_weight], np.float32
        )
        T = int(ti.shape[2])
        step = self._rerank_step(
            snap, field, kb, T, with_cnt, model, k_req, window, qb
        )
        with _LAUNCH_LOCK:
            out = step(ti, tw, tv, msm, qtoks, qvalid, weights)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        flops = scoring.text_plan_flops(slots, 0, 0) + (
            rerank_ops.rerank_flops(
                len(jobs), qb, min(kb, snap.n_docs_max),
                rview["tmax"], rview["dims"],
            )
            * snap.e_pad
        )
        return {
            "snap": snap, "out": out, "flops": flops, "rows": rows,
            "rescored": (model, spec, window),
        }

    def dispatch_serve(self, jobs, kb: int):
        snap = self.ensure_snapshot()
        plan0 = jobs[0].plan
        fields = plan0.fields
        t_cap = mesh_t_max()
        rows = self._rows_for(snap, len(jobs))
        ti_f, tw_f, tv_f, t_shapes = [], [], [], []
        slots = 0
        for f in fields:
            view = self._text_view(snap, f)
            ti, tw, tv, T, s = self._pack_serve_field(
                snap, view, jobs, f, t_cap, rows
            )
            ti_f.append(ti)
            tw_f.append(tw)
            tv_f.append(tv)
            t_shapes.append(T)
            slots += s
        msm = np.ones(rows, np.int32)
        msm[: len(jobs)] = [j.plan.msm for j in jobs]
        step = self._text_step(
            snap, fields, kb, tuple(t_shapes), True, True,
            plan0.combine, float(plan0.tie),
        )
        with _LAUNCH_LOCK:
            out = step(tuple(ti_f), tuple(tw_f), tuple(tv_f), msm)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        flops = scoring.text_plan_flops(slots, 0, 0)
        return {"snap": snap, "out": out, "flops": flops, "rows": rows}

    def collect_match(self, jobs, pend):
        self._collect_text(jobs, pend)

    collect_serve = collect_match

    def _collect_text(self, jobs, pend):
        snap = pend["snap"]
        ms, me, md, tot = jax.device_get(pend["out"])
        rescored = pend.get("rescored")
        if rescored is not None:
            from ..models import rerank as rerank_model

            _model, _spec, window = rescored
        for ji, j in enumerate(jobs):
            finite = np.isfinite(ms[ji])
            hits = [
                self._hit(snap, float(s), int(e), int(d))
                for s, e, d in zip(
                    ms[ji][finite][: j.k],
                    me[ji][finite][: j.k],
                    md[ji][finite][: j.k],
                )
            ]
            if rescored is not None:
                rerank_model.note_rescore(window, device=True)
            j.result = MeshTopDocs(
                total=int(tot[ji]),
                relation="eq",
                max_score=hits[0].score if hits else None,
                hits=hits,
                snapshot=snap,
            )
            j.finish()

    def dispatch_sparse(self, jobs, kb: int):
        """One SPMD learned-sparse launch for a same-(field, spec) job
        group. The `sparse.score` fault site fires with mesh=1 here —
        an injected error degrades the whole request to the per-shard
        path (indices._mesh_search's fallback), where the site fires
        again per segment with the host dense oracle as the terminal
        backstop."""
        from ..common.faults import faults as _faults
        from ..ops import impact as impact_ops
        from ..search import sparse as sparse_mod

        snap = self.ensure_snapshot()
        plan0 = jobs[0].plan
        field = plan0.field
        quantized = bool(plan0.spec.quantized)
        _faults.check("sparse.score", field=field, mesh=1)
        view = self._sparse_view(snap, field, quantized)
        rows = self._rows_for(snap, len(jobs))
        ti, tw, tv, T, slots = self._pack_sparse(
            snap, view, jobs, quantized, mesh_t_max(), rows
        )
        step = self._sparse_step(snap, field, quantized, kb, T)
        with _LAUNCH_LOCK:
            out = step(ti, tw, tv)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        sparse_mod.note_search(len(jobs), quantized, slots, 0)
        flops = impact_ops.sparse_flops(slots)
        return {"snap": snap, "out": out, "flops": flops, "rows": rows}

    def collect_sparse(self, jobs, pend):
        self._collect_text(jobs, pend)

    def dispatch_knn(self, jobs, kb: int):
        snap = self.ensure_snapshot()
        field = jobs[0].plan.field
        if any(j.plan.boost <= 0.0 for j in jobs):
            # a zero/negative boost would reorder under the
            # post-selection multiply — same host-merge rule as the
            # sequential collect
            raise MeshUnavailable("non-positive knn boost")
        spec = jobs[0].plan.ann  # shared: ann rides the group key
        if spec is not None:
            # IVF tier on the mesh: the `ann.probe` fault site fires
            # here too (ctx mesh=1) — an injected error surfaces to
            # _mesh_search, which degrades to the per-shard path (its
            # own ann.probe checks then prove the exact fallback)
            from ..common.faults import faults as _faults

            _faults.check("ann.probe", field=field, mesh=1)
            view = self._ann_view(snap, field, spec)
        else:
            view = self._knn_view(snap, field)
        dims = view["dims"]
        n_max = snap.n_docs_max
        rows = self._rows_for(snap, len(jobs))
        q = np.zeros((rows, dims), np.float32)
        nc = np.zeros((snap.e_pad, rows), np.int32)
        max_nc = 1
        for ji, j in enumerate(jobs):
            if len(j.plan.vector) != dims:
                raise MeshUnavailable("query vector dims mismatch")
            q[ji] = np.asarray(j.plan.vector, np.float32)
            for e in range(len(snap.entries)):
                n = int(view["n_per_entry"][e])
                if n:
                    nc[e, ji] = min(j.plan.num_candidates, n)
            max_nc = max(max_nc, min(j.plan.num_candidates, n_max))
        kc = min(max(scoring.next_bucket(max_nc, 16), 16), n_max)
        if spec is not None:
            from ..ops import ivf
            from ..search import ann as ann_mod

            step = self._ann_step(snap, field, spec, kc)
            with _LAUNCH_LOCK:
                out = step(q, nc)
            with self._lock:
                self.stats["launches"] += 1
                self.stats["jobs"] += len(jobs)
            flops = sum(
                ivf.ann_flops(
                    len(jobs), nl, spec.nprobe, view["cmax"], dims
                )
                for nl in view["nlists"]
            )
            for nl in view["nlists"]:
                ann_mod.note_search(spec.nprobe, nl, jobs=len(jobs))
            return {"snap": snap, "out": out, "flops": flops, "rows": rows}
        step = self._knn_step(snap, field, kc)
        with _LAUNCH_LOCK:
            out = step(q, nc)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        total_docs = int(view["n_per_entry"].sum())
        flops = scoring.knn_flops(len(jobs), total_docs, dims)
        return {"snap": snap, "out": out, "flops": flops, "rows": rows}

    def collect_knn(self, jobs, pend):
        from ..common.faults import faults

        faults.check("knn.collect", jobs=len(jobs), mesh=1)
        snap = pend["snap"]
        ms, me, md, counts = jax.device_get(pend["out"])
        shard_of = [sid for sid, _si in snap.entries]
        n_entries = len(shard_of)
        for ji, j in enumerate(jobs):
            boost = j.plan.boost
            # the sequential path cuts at k PER SHARD (each shard's
            # page is its top min(plan.k, size) after the nc rank cut)
            # before the coordinator's global page: walk the ordered
            # stream applying the same per-shard caps
            cap_shard = min(j.plan.k, j.k)
            taken: Dict[int, int] = {}
            hits: List[MeshHit] = []
            row_s, row_e, row_d = ms[ji], me[ji], md[ji]
            for pos in range(len(row_s)):
                s = row_s[pos]
                if not np.isfinite(s):
                    break  # score-desc stream: only -inf padding left
                e = int(row_e[pos])
                if e >= n_entries:  # pragma: no cover - padded entry
                    continue
                sid = shard_of[e]
                got = taken.get(sid, 0)
                if got >= cap_shard:
                    continue
                taken[sid] = got + 1
                hits.append(
                    self._hit(snap, float(s) * boost, e, int(row_d[pos]))
                )
                if len(hits) >= j.k:
                    break
            # the sequential coordinator's total is Σ per-shard totals,
            # each capped at k — reproduce it from the per-entry counts
            per_shard: Dict[int, int] = {}
            for e, sid in enumerate(shard_of):
                per_shard[sid] = per_shard.get(sid, 0) + int(counts[ji, e])
            total = sum(min(c, j.plan.k) for c in per_shard.values())
            j.result = MeshTopDocs(
                total=total,
                relation="eq",
                max_score=hits[0].score if hits else None,
                hits=hits,
                snapshot=snap,
            )
            j.finish()

    # ---- mesh aggregations (one SPMD launch per agg-body group) ----

    def compile_agg(self, nodes, mplan, mappings) -> "MeshAggPlan":
        """Compiles a size:0 agg body for the mesh step. Supported on
        this path: metric leaves sum/avg/min/max/value_count/stats,
        keyword terms, histogram / date_histogram (fixed intervals) —
        all WITHOUT subs; anything else raises MeshUnavailable and the
        per-shard path (with its own device engine) serves the request.
        The same float-exactness profiles as search/aggs_device gate
        routing, with the sum window tightened to the GLOBAL Σ|v| since
        psum accumulates float32 partial sums across the whole index."""
        from ..index.mapping import KEYWORD
        from ..search import aggs_device
        from ..search.aggs import PIPELINE_TYPES, _int_param, _norm_order
        from ..search.aggs_device import (
            I32_SUM_BOUND,
            _METRIC_KINDS,
            _NEEDS_CMP,
            _NEEDS_SUM,
            _parse_dh_interval,
        )

        snap = self.ensure_snapshot()
        specs = []
        for node in nodes:
            if node.type in PIPELINE_TYPES:
                continue
            if node.subs:
                raise MeshUnavailable("mesh aggs do not nest")
            if node.type in _METRIC_KINDS and node.type != "percentiles":
                field = node.params.get("field")
                if field is None:
                    raise MeshUnavailable("metric without a field")
                mf = mappings.get(field)
                if mf is not None and mf.type in ("keyword", "text"):
                    raise MeshUnavailable("keyword metric")
                abs_total = 0.0
                for sid, si in snap.entries:
                    p = aggs_device.col_profile(
                        snap.executors[sid], si, field
                    )
                    abs_total += p.abs_sum
                    if node.type in _NEEDS_SUM and not (
                        not p.present or p.n_exist == 0 or p.integer_valued
                    ):
                        raise MeshUnavailable("non-integer sum column")
                    if node.type in _NEEDS_CMP and not p.cmp_exact:
                        raise MeshUnavailable("non-f32-exact column")
                if node.type in _NEEDS_SUM and abs_total >= I32_SUM_BOUND:
                    raise MeshUnavailable("sum outside the int32 window")
                specs.append(
                    ("metric", node.name, node.type, field)
                )
            elif node.type == "terms":
                field = node.params.get("field")
                mf = mappings.get(field) if field else None
                if mf is None or mf.type != KEYWORD:
                    raise MeshUnavailable("mesh terms needs keyword")
                order = _norm_order(
                    node.params.get("order", {"_count": "desc"})
                )
                if next(iter(order)) not in ("_count", "_key"):
                    raise MeshUnavailable("terms order")
                size = _int_param(node, "size", 10)
                shard_size = _int_param(
                    node, "shard_size", max(int(size * 1.5) + 10, size)
                )
                specs.append(
                    ("terms_kw", node.name, field, size, shard_size,
                     tuple(order.items()))
                )
            elif node.type in ("histogram", "date_histogram"):
                field = node.params.get("field")
                if field is None:
                    raise MeshUnavailable("histogram without a field")
                date = node.type == "date_histogram"
                if date:
                    interval, cal = _parse_dh_interval(node.params)
                    if cal is not None:
                        raise MeshUnavailable("calendar interval")
                    offset = 0
                else:
                    interval = float(node.params.get("interval", 0))
                    offset = float(node.params.get("offset", 0))
                    if (
                        interval <= 0
                        or interval != int(interval)
                        or offset != int(offset)
                    ):
                        raise MeshUnavailable("non-integer interval")
                for sid, si in snap.entries:
                    p = aggs_device.col_profile(
                        snap.executors[sid], si, field
                    )
                    if p.present and p.n_exist and not p.integer_valued:
                        raise MeshUnavailable("non-integer histogram col")
                specs.append(
                    ("histo", node.name, field, int(interval), int(offset),
                     date)
                )
            else:
                raise MeshUnavailable(f"mesh agg type [{node.type}]")
        return MeshAggPlan(nodes, specs, mplan)

    def dispatch_agg(self, jobs, kb: int):
        # `kb`: the dispatch_* signature; an aggregation pages no top-k
        snap = self.ensure_snapshot()
        plan0 = jobs[0].plan
        rows = self._rows_for(snap, len(jobs))
        node_descs = []
        collect_meta = []
        for spec in plan0.specs:
            kind = spec[0]
            if kind == "metric":
                view = self._agg_num_view(snap, spec[3])
                node_descs.append(
                    ("metric", view["values"], view["ivalues"],
                     view["exists"])
                )
                collect_meta.append((spec, None))
            elif kind == "terms_kw":
                view = self._agg_ord_view(snap, spec[2])
                nbpad = scoring.next_bucket(
                    max(len(view["gterms"]), 1), 16
                )
                node_descs.append(
                    ("counts_entry", view["gords"], view["edocs"],
                     view["evalid"], nbpad)
                )
                collect_meta.append((spec, view["gterms"]))
            else:  # histo
                view = self._agg_histo_view(
                    snap, spec[2], spec[3], spec[4]
                )
                node_descs.append(
                    ("counts_doc", view["ids"], view["exists"],
                     view["nbpad"])
                )
                collect_meta.append((spec, view["qmin"]))
        with_cnt = any(j.plan.msm > 1 for j in jobs)
        if plan0.mplan is not None:
            field = plan0.mplan.field
            tview = self._text_view(snap, field)
            ti, tw, tv, T, slots = self._pack_match(
                snap, tview, jobs, mesh_t_max(), rows
            )
            text = (
                tview["doc_ids"], tview["tfs"], tview["inv_norm"]
            )
        else:
            field = None
            T = 1
            slots = 0
            ti = np.zeros((snap.e_pad, rows, 1), np.int32)
            tw = np.zeros((snap.e_pad, rows, 1), np.float32)
            tv = np.zeros((snap.e_pad, rows, 1), bool)
            text = None
        msm = np.ones(rows, np.int32)
        msm[: len(jobs)] = [j.plan.msm for j in jobs]
        key = ("agg", plan0.sig, field, T, rows, with_cnt)
        step = snap.steps.get(key)
        if step is None:
            with self._lock:
                step = snap.steps.get(key)
                if step is None:
                    step = build_mesh_agg_step(
                        snap.mesh, snap.live, node_descs, text,
                        with_cnt,
                    )
                    snap.steps[key] = step
        with _LAUNCH_LOCK:
            out = step(ti, tw, tv, msm)
        with self._lock:
            self.stats["launches"] += 1
            self.stats["jobs"] += len(jobs)
        n_total = sum(
            snap.readers[sid].segments[si].num_docs
            for sid, si in snap.entries
        )
        from ..ops.agg_kernels import agg_flops

        flops = scoring.text_plan_flops(slots, 0, 0) + agg_flops(
            n_total, len(node_descs)
        )
        return {
            "snap": snap, "out": out, "meta": collect_meta,
            "flops": flops, "rows": rows,
        }

    def collect_agg(self, jobs, pend):
        from ..search import aggs_device
        from ..search.aggs import _bkey, _order_buckets
        from ..search.aggs_device import _metric_partial

        snap = pend["snap"]
        outs = jax.device_get(pend["out"])
        totals, maxs = outs[0], outs[1]
        for ji, j in enumerate(jobs):
            partials = {}
            idx = 2
            for spec, extra in pend["meta"]:
                kind, name = spec[0], spec[1]
                if kind == "metric":
                    c = int(outs[idx][ji])
                    s = float(outs[idx + 1][ji])
                    mn = float(outs[idx + 2][ji])
                    mx = float(outs[idx + 3][ji])
                    idx += 4
                    partials[name] = _metric_partial(
                        spec[2], c, s if c else 0.0,
                        mn if c else None, mx if c else None,
                    )
                elif kind == "terms_kw":
                    row = np.asarray(outs[idx][ji])
                    idx += 1
                    gterms = extra
                    counts = {
                        gterms[int(o)]: int(row[o])
                        for o in np.nonzero(row[: len(gterms)])[0]
                    }
                    _sp, _name, _field, size, shard_size, order_t = spec
                    order = dict(order_t)
                    top = _order_buckets(counts, order)[:shard_size]
                    shard_error = (
                        top[-1][1]
                        if len(counts) > shard_size and top
                        else 0
                    )
                    partials[name] = {
                        "t": "terms",
                        "buckets": {
                            _bkey(k): {
                                "key": k, "doc_count": c2, "subs": {}
                            }
                            for k, c2 in top
                        },
                        "sum_docs": sum(counts.values()),
                        "size": size,
                        "order": order,
                        "shard_error": shard_error,
                    }
                else:  # histo
                    row = np.asarray(outs[idx][ji])
                    idx += 1
                    qmin = extra
                    _sp, _name, _field, interval, offset, date = spec
                    buckets = {}
                    for rel in np.nonzero(row)[0]:
                        raw = (qmin + int(rel)) * interval + offset
                        k = int(raw) if date else float(raw)
                        buckets[k] = {
                            "key": k,
                            "doc_count": int(row[rel]),
                            "subs": {},
                        }
                    partials[name] = {
                        "t": "date_histogram" if date else "histogram",
                        "buckets": buckets,
                    }
            mx = float(maxs[ji])
            j.result = {
                "total": int(totals[ji]),
                "max_score": mx if np.isfinite(mx) else None,
                "partials": partials,
                "snapshot": snap,
            }
            j.finish()

    def _hit(self, snap, score, entry, doc) -> MeshHit:
        sid, si = snap.entries[entry]
        return MeshHit(
            score=score,
            shard=sid,
            segment=si,
            local_doc=doc,
            doc_id=snap.readers[sid].segments[si].doc_ids[doc],
        )

    def note_routed(self) -> None:
        with self._lock:
            self.stats["routed"] += 1

    def note_fallback(self) -> None:
        with self._lock:
            self.stats["fallbacks"] += 1

    def note_degraded(self) -> None:
        with self._lock:
            self.stats["degraded"] += 1

    def node_stats(self) -> Dict[str, dict]:
        with self._lock:
            return {path: {k: self.stats[k] for k in block}
                    for path, block in self.NODE_STATS.items()}

    def stats_snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
        snap = self._snapshot
        out["entries"] = len(snap.entries) if snap and not snap.closed else 0
        out["devices"] = len(self.device_ids)
        return out
