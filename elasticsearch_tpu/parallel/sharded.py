"""Sharded (multi-chip) search execution over a device mesh.

Reference analog: the coordinator scatter/gather pipeline
(`TransportSearchAction` → per-shard `SearchService.executeQueryPhase` →
`SearchPhaseController.reducedQueryPhase`, SURVEY.md §3.3). The TPU-native
redesign collapses the whole round-trip into ONE SPMD program:

  - every shard's tiled postings live stacked on the ``shards`` mesh axis
    (`doc_ids[S, T, 128]` with `PartitionSpec('shards', None, None)`);
  - a query batch is sharded over the ``data`` axis (many concurrent
    searches — the ES coordinator's in-flight search set);
  - inside `shard_map`, each device scores ITS shard for ITS slice of the
    query batch (QueryPhase), takes a local top-k, and the shard-merge
    (`QueryPhaseResultConsumer` / reduce) is a `lax.all_gather` over the
    ICI followed by a k-way `top_k` — no transport layer, no
    serialization, no per-shard RPC correlation.

Tie-break parity: Lucene's coordinator merge orders (score desc,
shard asc, doc asc). `lax.top_k` keeps the lowest index among equal
scores, and the gathered axis is laid out shard-major with per-shard
results already doc-ascending among ties, so the merged ordering matches.

Totals (`hits.total.value`) reduce with a `psum` over ``shards`` — the
analog of summing each shard's `QuerySearchResult.totalHits`.

Shard folding: the stacked axis may carry MORE entries than the mesh's
``shards`` axis has devices — entries are padded to ``axis * fold`` rows
and each device vmaps over its ``fold`` local entries before the ICI
merge, so non-power-of-two layouts and fewer-devices-than-shards both
work (parallel/mesh.py fold_factor).

Two families of step builders live here:

  * ``build_sharded_bm25_step`` / ``build_sharded_knn_step`` — the
    original ShardedIndex demo steps (driver dryrun, tests);
  * ``build_mesh_text_step`` / ``build_mesh_knn_step`` — the SERVING
    steps behind `parallel/mesh_executor.MeshExecutor`: stacked entries
    are (shard, segment) pairs so per-entry scoring reproduces the
    sequential per-segment kernels float-exactly (same tile plans, same
    scatter order, same live-mask semantics), and only the merge moves
    from the host to the ICI.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.segment import INVALID_DOC, TILE, Segment
from ..models import bm25
from ..ops.scoring import _score_tiles_inner, bm25_tile_contrib, next_bucket
from .mesh import DATA_AXIS, SHARD_AXIS, fold_factor

shard_map = jax.shard_map


class ShardedTopK(NamedTuple):
    scores: jax.Array  # float32[B, k] merged, score desc
    global_docs: jax.Array  # int32[B, k] doc_base[shard] + local doc (-1 pad)
    totals: jax.Array  # int32[B] total matching docs across shards


@dataclass
class _ShardPostings:
    """Host-side per-shard postings handle for one field."""

    segment: Segment
    field: str
    inv_norm: np.ndarray  # float32[n_docs_padded]


class ShardedIndex:
    """Stacks S single-shard segments into mesh-sharded device arrays.

    The ES analog of an index with `number_of_shards: S` whose shards are
    pinned one-per-chip (BASELINE.json north star: "shards pinned to
    distinct chips"). Each shard is an independent Segment (its own term
    dictionary, norms, stats — exactly like an ES shard is a full Lucene
    index); this class pads them to a common dense shape and lays the
    stack out over the ``shards`` mesh axis. With fewer devices than
    shards the stack is padded to ``axis * fold`` rows and each device
    scores its fold of shards (mesh.py fold_factor).
    """

    def __init__(
        self,
        mesh: Mesh,
        segments: Sequence[Segment],
        field: str,
        k1: float = bm25.DEFAULT_K1,
        b: float = bm25.DEFAULT_B,
        vector_field: Optional[str] = None,
    ):
        g = mesh.shape[SHARD_AXIS]
        self.fold = fold_factor(mesh, len(segments))
        if g * self.fold < len(segments):
            raise ValueError(
                f"{len(segments)} shards but mesh '{SHARD_AXIS}' axis is "
                f"{g} (fold {self.fold})"
            )
        self.mesh = mesh
        self.segments = list(segments)
        self.field = field
        self.n_shards = len(segments)
        # stacked rows: shards padded to an equal fold per device
        self.n_stack = g * self.fold
        self.k1 = k1
        self.b = b

        # ---- per-shard BM25 term weights (each shard uses ITS OWN stats,
        # like per-shard IDF without the optional DFS phase) ----
        self._weights: List[Dict[str, float]] = []
        self._inv_norms: List[np.ndarray] = []
        n_tiles_max = 1
        n_docs_max = 1
        for seg in self.segments:
            pf = seg.postings.get(field)
            if pf is None or pf.n_tiles == 0:
                self._weights.append({})
                self._inv_norms.append(np.zeros(max(seg.num_docs, 1), np.float32))
                n_docs_max = max(n_docs_max, max(seg.num_docs, 1))
                continue
            st = pf.stats
            doc_count = st.doc_count or 1
            avgdl = bm25.avg_field_length(st.sum_total_term_freq, doc_count)
            cache = bm25.norm_inverse_cache(avgdl, k1, b)
            self._weights.append(
                {
                    t: float(bm25.idf(doc_count, int(pf.term_df[i])))
                    for i, t in enumerate(pf.terms)
                }
            )
            self._inv_norms.append(cache[pf.norms.astype(np.int64)])
            n_tiles_max = max(n_tiles_max, pf.n_tiles)
            n_docs_max = max(n_docs_max, seg.num_docs)
        self.n_docs_max = n_docs_max
        self.n_tiles_max = n_tiles_max

        # ---- stacked, padded device arrays sharded over 'shards' ----
        S = self.n_stack
        doc_ids = np.full((S, n_tiles_max, TILE), INVALID_DOC, np.int32)
        tfs = np.zeros((S, n_tiles_max, TILE), np.int32)
        inv_norm = np.zeros((S, n_docs_max), np.float32)
        doc_base = np.zeros(S, np.int32)
        base = 0
        for si, seg in enumerate(self.segments):
            pf = seg.postings.get(field)
            if pf is not None and pf.n_tiles:
                doc_ids[si, : pf.n_tiles] = pf.doc_ids
                tfs[si, : pf.n_tiles] = pf.tfs
            inv_norm[si, : len(self._inv_norms[si])] = self._inv_norms[si]
            doc_base[si] = base
            base += seg.num_docs
        self.total_docs = base

        shard3 = NamedSharding(mesh, P(SHARD_AXIS, None, None))
        shard2 = NamedSharding(mesh, P(SHARD_AXIS, None))
        shard1 = NamedSharding(mesh, P(SHARD_AXIS))
        self.doc_ids = jax.device_put(doc_ids, shard3)
        self.tfs = jax.device_put(tfs, shard3)
        self.inv_norm = jax.device_put(inv_norm, shard2)
        self.doc_base = jax.device_put(doc_base, shard1)

        # ---- optional dense-vector shard stack ----
        self.vector_field = vector_field
        self.vectors = None
        self.vec_exists = None
        if vector_field is not None:
            dims = None
            for seg in self.segments:
                vf = seg.vectors.get(vector_field)
                if vf is not None:
                    dims = vf.vectors.shape[1]
                    break
            if dims is not None:
                vecs = np.zeros((S, n_docs_max, dims), np.float32)
                exists = np.zeros((S, n_docs_max), bool)
                for si, seg in enumerate(self.segments):
                    vf = seg.vectors.get(vector_field)
                    if vf is None:
                        continue
                    mat = (
                        vf.unit_vectors
                        if vf.similarity == "cosine" and vf.unit_vectors is not None
                        else vf.vectors
                    )
                    vecs[si, : seg.num_docs] = mat
                    exists[si, : seg.num_docs] = vf.exists
                self.vectors = jax.device_put(vecs, shard3)
                self.vec_exists = jax.device_put(exists, shard2)

    # ---- host-side query compilation (the per-shard Weight creation) ----

    def compile_queries(
        self,
        term_lists: Sequence[Sequence[str]],
        operators: Optional[Sequence[str]] = None,
        bucket: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Queries → per-(shard, query) padded tile plans.

        Returns (tile_idx[S,B,T], tile_w[S,B,T], tile_v[S,B,T], msm[B]).
        Each shard resolves the same terms against its own dictionary and
        stats — the analog of per-shard `Weight` creation in
        `SearchService.executeQueryPhase`. S is the padded stack size
        (folded layouts score all-invalid padding rows to -inf).
        """
        B = len(term_lists)
        plans: List[List[Tuple[List[int], List[float]]]] = []
        t_max = 1
        for si, seg in enumerate(self.segments):
            pf = seg.postings.get(self.field)
            shard_plans: List[Tuple[List[int], List[float]]] = []
            for terms in term_lists:
                idxs: List[int] = []
                ws: List[float] = []
                if pf is not None:
                    for t in terms:
                        tid = pf.term_id(t)
                        if tid < 0:
                            continue
                        start = int(pf.term_tile_start[tid])
                        cnt = int(pf.term_tile_count[tid])
                        w = self._weights[si].get(t, 0.0)
                        idxs.extend(range(start, start + cnt))
                        ws.extend([w] * cnt)
                t_max = max(t_max, len(idxs))
                shard_plans.append((idxs, ws))
            plans.append(shard_plans)
        T = bucket or next_bucket(t_max)
        S = self.n_stack
        tile_idx = np.zeros((S, B, T), np.int32)
        tile_w = np.zeros((S, B, T), np.float32)
        tile_v = np.zeros((S, B, T), bool)
        for si in range(self.n_shards):
            for bi, (idxs, ws) in enumerate(plans[si]):
                t = len(idxs)
                tile_idx[si, bi, :t] = idxs
                tile_w[si, bi, :t] = ws
                tile_v[si, bi, :t] = True
        msm = np.ones(B, np.int32)
        if operators is not None:
            for bi, op in enumerate(operators):
                if op == "and":
                    msm[bi] = len(term_lists[bi])
        return tile_idx, tile_w, tile_v, msm


def _merge_gathered(gs, gd, k: int):
    """ICI merge epilogue shared by every step: gathered per-entry pages
    [G, F, Bd, kk] → (scores[Bd, K], entry[Bd, K], doc[Bd, K]). Slots
    are laid out entry-major (shard/segment asc) with per-entry ranks
    already doc-ascending among ties, and lax.top_k keeps the lowest
    slot among equals — the coordinator's (score desc, shard asc, rank
    asc) merge order, on device."""
    G, F, Bd, kk = gs.shape
    slots = G * F * kk
    gs2 = jnp.transpose(gs, (2, 0, 1, 3)).reshape(Bd, slots)
    gd2 = jnp.transpose(gd, (2, 0, 1, 3)).reshape(Bd, slots)
    K = min(k, slots)
    ms, mi = jax.lax.top_k(gs2, K)
    entry_of_slot = jnp.arange(slots, dtype=jnp.int32) // kk
    me = entry_of_slot[mi]
    md = jnp.take_along_axis(gd2, mi, axis=1)
    return ms, me, md


def build_sharded_bm25_step(index: ShardedIndex, k: int):
    """Jitted SPMD search step: per-shard score+top-k, ICI merge.

    fn(tile_idx[S,B,T], tile_w, tile_v, msm[B]) -> ShardedTopK with the
    query batch B sharded over the ``data`` axis and postings over
    ``shards``; the returned top-k is replicated over ``shards`` and
    sharded over ``data``. S is the padded stack (fold per device).
    """
    mesh = index.mesh
    n_docs = index.n_docs_max

    def body(doc_ids, tfs, inv_norm, doc_base, tile_idx, tile_w, tile_v, msm):
        # block shapes: doc_ids[F,T_all,128], tile_idx[F,Bd,T], msm[Bd]
        def entry(doc_ids_e, tfs_e, inv_e, base_e, ti_e, tw_e, tv_e):
            rows_doc = doc_ids_e[ti_e]  # [Bd, T, 128]
            rows_tf = tfs_e[ti_e]

            def one(rd, rt, w, v, m):
                scores, cnt = _score_tiles_inner(rd, rt, w, v, inv_e, n_docs)
                mask = cnt >= jnp.maximum(m, 1)
                masked = jnp.where(mask, scores, -jnp.inf)
                s, d = jax.lax.top_k(masked, min(k, n_docs))
                return s, d, mask.sum().astype(jnp.int32)

            s, d, t = jax.vmap(one)(rows_doc, rows_tf, tw_e, tv_e, msm)
            gdoc = jnp.where(s > -jnp.inf, d + base_e, -1)
            return s, gdoc, t

        s, gdoc, t = jax.vmap(entry)(
            doc_ids, tfs, inv_norm, doc_base, tile_idx, tile_w, tile_v
        )  # [F,Bd,k'] [F,Bd,k'] [F,Bd]
        # ---- shard merge over ICI (the coordinator reduce) ----
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, k']
        gd = jax.lax.all_gather(gdoc, SHARD_AXIS)
        ms, _, md = _merge_gathered(gs, gd, k)
        totals = jax.lax.psum(t.sum(axis=0), SHARD_AXIS)
        return ms, md, totals

    p_post3 = P(SHARD_AXIS, None, None)
    p_post2 = P(SHARD_AXIS, None)
    p_q = P(SHARD_AXIS, DATA_AXIS, None)
    p_out = P(DATA_AXIS, None)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(p_post3, p_post3, p_post2, P(SHARD_AXIS), p_q, p_q, p_q, P(DATA_AXIS)),
        out_specs=(p_out, p_out, P(DATA_AXIS)),
        check_vma=False,
    )

    @jax.jit
    def step(tile_idx, tile_w, tile_v, msm):
        s, d, t = fn(
            index.doc_ids,
            index.tfs,
            index.inv_norm,
            index.doc_base,
            tile_idx,
            tile_w,
            tile_v,
            msm,
        )
        return ShardedTopK(s, d, t)

    return step


def build_sharded_knn_step(index: ShardedIndex, k: int, similarity: str = "cosine"):
    """SPMD brute-force kNN: per-shard MXU matmul + top-k, ICI merge.

    fn(queries[B, d]) -> ShardedTopK. Queries sharded over ``data`` and
    replicated over ``shards``; one (B/d × d)·(d × N) matmul per chip —
    the reference's `KnnFloatVectorQuery` DFS round (SURVEY.md §3.4)
    without the graph walk.
    """
    if index.vectors is None:
        raise ValueError(f"index has no vector field [{index.vector_field}]")
    mesh = index.mesh

    def body(vectors, exists, doc_base, queries):
        q = queries
        if similarity == "cosine":
            qn = jnp.linalg.norm(q, axis=1, keepdims=True)
            q = q / jnp.where(qn == 0, 1.0, qn)

        def entry(vectors_e, exists_e, base_e):
            dots = q @ vectors_e.T  # [Bd, N] — MXU
            if similarity in ("cosine", "dot_product"):
                scores = (1.0 + dots) / 2.0
            elif similarity == "l2_norm":
                q2 = jnp.sum(q * q, axis=1, keepdims=True)
                v2 = jnp.sum(vectors_e * vectors_e, axis=1)[None, :]
                scores = 1.0 / (1.0 + jnp.maximum(q2 + v2 - 2.0 * dots, 0.0))
            elif similarity == "max_inner_product":
                scores = jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
            else:
                raise ValueError(f"unknown similarity [{similarity}]")
            scores = jnp.where(
                exists_e[None, :], scores.astype(jnp.float32), -jnp.inf
            )
            kk = min(k, scores.shape[1])
            s, d = jax.lax.top_k(scores, kk)
            gdoc = jnp.where(s > -jnp.inf, d + base_e, -1)
            t = jnp.sum(exists_e).astype(jnp.int32) * jnp.ones(
                s.shape[0], jnp.int32
            )
            return s, gdoc, t

        s, gdoc, t = jax.vmap(entry)(vectors, exists, doc_base)
        gs = jax.lax.all_gather(s, SHARD_AXIS)
        gd = jax.lax.all_gather(gdoc, SHARD_AXIS)
        ms, _, md = _merge_gathered(gs, gd, k)
        totals = jax.lax.psum(t.sum(axis=0), SHARD_AXIS)
        return ms, md, totals

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS),
            P(DATA_AXIS, None),
        ),
        out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS)),
        check_vma=False,
    )

    @jax.jit
    def step(queries):
        s, d, t = fn(index.vectors, index.vec_exists, index.doc_base, queries)
        return ShardedTopK(s, d, t)

    return step


# ---------------------------------------------------------------------------
# Serving SPMD steps — the production mesh path (MeshExecutor).
#
# The stacked axis carries (shard, segment) ENTRIES, not whole shards:
# the sequential serving path scores per segment (ChunkedScorer /
# MultiFusedScorer accumulate one segment's doc space), so keeping the
# per-entry granularity makes the mesh program reproduce the sequential
# kernels value-for-value — same tile plans in the same scatter order,
# same `w - w/(1 + tf·inv)` BM25 formula, same live/count masking — and
# only the cross-segment + cross-shard merge moves from S host round
# trips to one all_gather + top_k on the ICI. Entry order is (shard asc,
# segment asc), so the device merge's (score desc, slot asc) ordering is
# exactly the coordinator's (score desc, shard asc, segment asc, doc
# asc) tie-break.
# ---------------------------------------------------------------------------


def build_mesh_text_step(
    mesh: Mesh,
    doc_ids_f: Sequence[jax.Array],  # per field: [E, Tmax_f, TILE] stacked
    tfs_f: Sequence[jax.Array],
    inv_norm_f: Sequence[jax.Array],  # per field: [E, Nmax]
    live: jax.Array,  # bool[E, Nmax] (live docs ∧ in-range padding mask)
    k: int,
    *,
    with_cnt: bool,
    count_signed: bool,
    combine: str = "sum",
    tie: float = 0.0,
):
    """One SPMD text-scoring step over stacked (shard, segment) entries.

    fn(ti_f..., tw_f..., tv_f..., msm[B]) →
        (scores[B, K], entry[B, K], doc[B, K], totals[B])
    with per-field plans ti/tw/tv of shape [E, B, T_f] sharded
    (shards, data, None) and the outputs sharded over ``data`` only.

    * ``count_signed`` (the ServePlan families): |w| scores, w > 0
      counts toward msm — the MultiFusedScorer weight-sign convention.
    * ``with_cnt`` False (pure-disjunction match groups): the match mask
      is ``acc > 0`` exactly like ops/scoring._finalize with cnt=None.
    * ``combine``: "sum" (bool / most_fields) or "max_tie"
      (best_fields: max + tie·(sum − max)).
    """
    F_fields = len(doc_ids_f)
    n_docs = int(inv_norm_f[0].shape[1])
    tie_f = jnp.float32(tie)

    def body(*args):
        it = iter(args)
        d_f = [next(it) for _ in range(F_fields)]  # [F, Tmax, TILE] blocks
        t_f = [next(it) for _ in range(F_fields)]
        i_f = [next(it) for _ in range(F_fields)]
        live_b = next(it)  # [F, Nmax]
        ti_f = [next(it) for _ in range(F_fields)]  # [F, Bd, T]
        tw_f = [next(it) for _ in range(F_fields)]
        tv_f = [next(it) for _ in range(F_fields)]
        msm = next(it)  # [Bd]

        def entry(per_field, live_e):
            Bd = per_field[0][3].shape[0]
            cnt = (
                jnp.zeros((Bd, n_docs + 1), jnp.int32) if with_cnt else None
            )
            accs = []
            for dids, tfs_, inv, ti, tw, tv in per_field:
                nt = dids.shape[0]
                rows_d = dids[jnp.clip(ti, 0, nt - 1)]  # [Bd, T, 128]
                rows_t = tfs_[jnp.clip(ti, 0, nt - 1)]
                valid = (rows_d >= 0) & tv[:, :, None]
                w = (jnp.abs(tw) if count_signed else tw)[:, :, None]
                tgt, s = bm25_tile_contrib(
                    rows_d, rows_t, w, valid, inv, n_docs
                )
                acc = jnp.zeros((Bd, n_docs + 1), jnp.float32)
                acc = jax.vmap(
                    lambda a, d, v: a.at[d.ravel()].add(v.ravel())
                )(acc, tgt, s)
                accs.append(acc[:, :n_docs])
                if with_cnt:
                    counted = (
                        valid & (tw > 0)[:, :, None] if count_signed else valid
                    )
                    cnt = jax.vmap(
                        lambda c, d, v: c.at[d.ravel()].add(
                            v.ravel().astype(jnp.int32)
                        )
                    )(cnt, tgt, counted)
            if len(accs) == 1:
                combined = accs[0]
            elif combine == "sum":
                combined = accs[0]
                for a in accs[1:]:
                    combined = combined + a
            else:  # max_tie (DisjunctionMaxQuery)
                stack = jnp.stack(accs)
                best = stack.max(axis=0)
                combined = best + tie_f * (stack.sum(axis=0) - best)
            if with_cnt:
                mask = cnt[:, :n_docs] >= jnp.maximum(msm, 1)[:, None]
            else:
                mask = combined > 0
            mask = mask & live_e[None, :]
            masked = jnp.where(mask, combined, -jnp.inf)
            kk = min(k, n_docs)
            s, d = jax.lax.top_k(masked, kk)
            return s, d, mask.sum(axis=1, dtype=jnp.int32)

        per_entry = tuple(
            tuple(x[fi] for x in (d_f, t_f, i_f, ti_f, tw_f, tv_f))
            for fi in range(F_fields)
        )
        s, d, t = jax.vmap(
            lambda pf, le: entry(pf, le)
        )(per_entry, live_b)  # [F, Bd, kk] ×2, [F, Bd]
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, kk]
        gd = jax.lax.all_gather(d, SHARD_AXIS)
        ms, me, md = _merge_gathered(gs, gd, k)
        totals = jax.lax.psum(t.sum(axis=0), SHARD_AXIS)
        return ms, me, md, totals

    p3 = P(SHARD_AXIS, None, None)
    p2 = P(SHARD_AXIS, None)
    p_plan = P(SHARD_AXIS, DATA_AXIS, None)
    p_out = P(DATA_AXIS, None)
    in_specs = (
        tuple(p3 for _ in range(2 * F_fields))
        + tuple(p2 for _ in range(F_fields))
        + (p2,)
        + tuple(p_plan for _ in range(3 * F_fields))
        + (P(DATA_AXIS),)
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(p_out, p_out, p_out, P(DATA_AXIS)),
        check_vma=False,
    )

    @jax.jit
    def step(ti_f, tw_f, tv_f, msm):
        args = (
            tuple(doc_ids_f) + tuple(tfs_f) + tuple(inv_norm_f) + (live,)
            + tuple(ti_f) + tuple(tw_f) + tuple(tv_f) + (msm,)
        )
        return fn(*args)

    return step


def build_mesh_sparse_step(
    mesh: Mesh,
    doc_ids: jax.Array,  # [E, Tmax, TILE] stacked impact-ordered tiles
    values: jax.Array,  # [E, Tmax, TILE] stored dtype (int8 or f32)
    live: jax.Array,  # bool[E, Nmax] (live docs ∧ in-range padding mask)
    k: int,
):
    """One SPMD learned-sparse scoring step over stacked (shard,
    segment) entries.

    fn(ti, tw, tv) → (scores[B, K], entry[B, K], doc[B, K], totals[B])
    with the per-(entry, job) tile plan ti/tw/tv of shape [E, B, T]
    sharded (shards, data, None) and outputs over ``data`` only.

    The contribution formula is ops/impact.impact_tile_contrib — the
    SAME jnp expression the sequential ImpactScorer launches — and the
    tile lists arrive term-ordered with every tile present (no pruning
    on the mesh path: theta would need a cross-device round-trip, and
    the full pass keeps the step float-identical to the per-shard
    serving path with the exact totals for free). `tw` carries the
    query weight with each ENTRY's per-term dequant scale pre-folded,
    so the one step serves int8 and fp32 columns alike."""
    from ..ops.impact import impact_tile_contrib

    n_docs = int(live.shape[1])

    def body(dids, vals, live_b, ti, tw, tv):
        def entry(d_e, v_e, live_e, ti_e, tw_e, tv_e):
            Bd = ti_e.shape[0]
            nt = d_e.shape[0]
            rows_d = d_e[jnp.clip(ti_e, 0, nt - 1)]  # [Bd, T, 128]
            rows_v = v_e[jnp.clip(ti_e, 0, nt - 1)]
            valid = (rows_d >= 0) & tv_e[:, :, None]
            tgt, s = impact_tile_contrib(
                rows_d, rows_v, tw_e[:, :, None], valid, n_docs
            )
            acc = jnp.zeros((Bd, n_docs + 1), jnp.float32)
            acc = jax.vmap(
                lambda a, d, v: a.at[d.ravel()].add(v.ravel())
            )(acc, tgt, s)
            cnt = jnp.zeros((Bd, n_docs + 1), jnp.int32)
            cnt = jax.vmap(
                lambda c, d, v: c.at[d.ravel()].add(
                    v.ravel().astype(jnp.int32)
                )
            )(cnt, tgt, valid)
            # every query term is optional: the sparse match mask is
            # cnt > 0, exactly ops/scoring._finalize at msm=1
            mask = (cnt[:, :n_docs] >= 1) & live_e[None, :]
            masked = jnp.where(mask, acc[:, :n_docs], -jnp.inf)
            kk = min(k, n_docs)
            s2, d2 = jax.lax.top_k(masked, kk)
            return s2, d2, mask.sum(axis=1, dtype=jnp.int32)

        s, d, t = jax.vmap(entry)(
            dids, vals, live_b, ti, tw, tv
        )  # [F, Bd, kk] ×2, [F, Bd]
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, kk]
        gd = jax.lax.all_gather(d, SHARD_AXIS)
        ms, me, md = _merge_gathered(gs, gd, k)
        totals = jax.lax.psum(t.sum(axis=0), SHARD_AXIS)
        return ms, me, md, totals

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None),
            P(SHARD_AXIS, DATA_AXIS, None),
            P(SHARD_AXIS, DATA_AXIS, None),
            P(SHARD_AXIS, DATA_AXIS, None),
        ),
        out_specs=(
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS),
        ),
        check_vma=False,
    )

    @jax.jit
    def step(ti, tw, tv):
        return fn(doc_ids, values, live, ti, tw, tv)

    return step


def build_mesh_rerank_step(
    mesh: Mesh,
    doc_ids: jax.Array,  # [E, Tmax, TILE] stacked postings tiles
    tfs: jax.Array,
    inv_norm: jax.Array,  # [E, Nmax]
    live: jax.Array,  # bool[E, Nmax]
    rr_starts: jax.Array,  # i32 [E, Nmax] local doc → flat token row
    rr_counts: jax.Array,  # i32 [E, Nmax]
    rr_toks: jax.Array,  # [E, Fmax, d] f32 (or int8 with scales)
    rr_scales: Optional[jax.Array],  # f32 [E, Fmax] or None
    kb: int,  # local candidate page (compile bucket >= k_req)
    k_req: int,  # the request page (from + size): per-entry page cut
    window: int,  # rescore window, already clamped to k_req
    tmax: int,  # max tokens per doc (gather width)
    *,
    with_cnt: bool,
):
    """One SPMD first-stage + RERANK step: per-entry BM25 scoring and
    local top-k exactly like build_mesh_text_step (single field), then
    the maxsim rescore runs LOCALLY per entry — each entry's
    rank_vectors tokens are sharded with it — so the ICI all_gather
    carries already-reranked candidates. With one live segment per
    shard (the routing precondition), each entry's local stream equals
    the per-shard path's post-rescore page: positions < window are
    re-sorted by blended score, positions [window, k_req) keep first
    stage, positions >= k_req are dropped (the shard page cut).

    fn(ti, tw, tv, msm[B], qtoks[B, Qt, d], qvalid[B, Qt],
       weights[2]) →
        (scores[B, slots], entry[B, slots], doc[B, slots], totals[B])
    The merged stream comes back FULLY ordered (score desc, slot asc =
    (entry, post-rescore rank) asc — the coordinator's (-score, shard,
    rank) tie-break) rather than cut at a global k, mirroring
    build_mesh_knn_step.
    """
    from ..ops.rerank import blend_and_sort, maxsim_candidates

    n_docs = int(inv_norm.shape[1])
    kk = min(kb, n_docs)
    wc = min(window, k_req, kk)
    has_scales = rr_scales is not None

    def body(d_b, t_b, i_b, live_b, st_b, ct_b, tk_b, sc_b, ti, tw, tv,
             msm, qtoks, qvalid, weights):
        def entry(args):
            dids, tfs_, inv, live_e, st_e, ct_e, tk_e, sc_e, ti_e, tw_e, tv_e = args
            Bd = ti_e.shape[0]
            nt = dids.shape[0]
            rows_d = dids[jnp.clip(ti_e, 0, nt - 1)]  # [Bd, T, 128]
            rows_t = tfs_[jnp.clip(ti_e, 0, nt - 1)]
            valid = (rows_d >= 0) & tv_e[:, :, None]
            tgt, s = bm25_tile_contrib(
                rows_d, rows_t, tw_e[:, :, None], valid, inv, n_docs
            )
            acc = jnp.zeros((Bd, n_docs + 1), jnp.float32)
            acc = jax.vmap(
                lambda a, d, v: a.at[d.ravel()].add(v.ravel())
            )(acc, tgt, s)
            acc = acc[:, :n_docs]
            if with_cnt:
                cnt = jnp.zeros((Bd, n_docs + 1), jnp.int32)
                cnt = jax.vmap(
                    lambda c, d, v: c.at[d.ravel()].add(
                        v.ravel().astype(jnp.int32)
                    )
                )(cnt, tgt, valid)
                mask = cnt[:, :n_docs] >= jnp.maximum(msm, 1)[:, None]
            else:
                mask = acc > 0
            mask = mask & live_e[None, :]
            masked = jnp.where(mask, acc, -jnp.inf)
            s_e, d_e = jax.lax.top_k(masked, kk)
            # ---- local rescore, before the gather: page cut at k_req,
            # maxsim over this entry's token block, window re-sort ----
            pos = jnp.arange(kk, dtype=jnp.int32)
            keep = jnp.isfinite(s_e) & (pos[None, :] < k_req)
            msim = maxsim_candidates(
                qtoks, qvalid, st_e, ct_e, tk_e,
                sc_e if has_scales else None,
                jnp.where(keep, d_e, 0), tmax,
            )
            first = jnp.where(keep, s_e, -jnp.inf)
            scores, perm = blend_and_sort(msim, first, keep, weights, wc)
            d_sorted = jnp.take_along_axis(d_e, perm, axis=1)
            return scores, d_sorted, mask.sum(axis=1, dtype=jnp.int32)

        per_entry = (
            d_b, t_b, i_b, live_b, st_b, ct_b, tk_b, sc_b, ti, tw, tv,
        )
        s, d, t = jax.vmap(entry)(per_entry)  # [F, Bd, kk] ×2, [F, Bd]
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, kk]
        gd = jax.lax.all_gather(d, SHARD_AXIS)
        G, F, Bd, _ = gs.shape
        slots = G * F * kk
        gs2 = jnp.transpose(gs, (2, 0, 1, 3)).reshape(Bd, slots)
        gd2 = jnp.transpose(gd, (2, 0, 1, 3)).reshape(Bd, slots)
        entry_of_slot = jnp.arange(slots, dtype=jnp.int32) // kk
        ms, mi = jax.lax.top_k(gs2, slots)
        me = entry_of_slot[mi]
        md = jnp.take_along_axis(gd2, mi, axis=1)
        totals = jax.lax.psum(t.sum(axis=0), SHARD_AXIS)
        return ms, me, md, totals

    p3 = P(SHARD_AXIS, None, None)
    p2 = P(SHARD_AXIS, None)
    p_plan = P(SHARD_AXIS, DATA_AXIS, None)
    p_out = P(DATA_AXIS, None)
    in_specs = (
        p3, p3, p2, p2,  # text view + live
        p2, p2, p3,  # rerank starts/counts/toks
        p2,  # scales (per-entry dummy when the model is float)
        p_plan, p_plan, p_plan,  # tile plans
        P(DATA_AXIS),  # msm
        P(DATA_AXIS, None, None),  # qtoks
        P(DATA_AXIS, None),  # qvalid
        P(),  # weights (replicated)
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(p_out, p_out, p_out, P(DATA_AXIS)),
        check_vma=False,
    )

    dummy_scales = (
        jnp.zeros((int(doc_ids.shape[0]), 1), jnp.float32)
        if rr_scales is None
        else rr_scales
    )

    @jax.jit
    def step(ti, tw, tv, msm, qtoks, qvalid, weights):
        return fn(
            doc_ids, tfs, inv_norm, live, rr_starts, rr_counts, rr_toks,
            dummy_scales, ti, tw, tv, msm, qtoks, qvalid, weights,
        )

    return step


def build_mesh_knn_step(
    mesh: Mesh,
    vectors: jax.Array,  # [E, Nmax, dims] stacked (original dtype)
    cand: jax.Array,  # bool[E, Nmax] exists ∧ live ∧ in-range
    similarity: str,
    kc: int,  # per-entry candidate page (≥ every job's num_candidates)
):
    """One SPMD brute-force kNN step over stacked (shard, segment)
    entries with the sequential path's per-(job, entry) num_candidates
    rank cut applied on device.

    fn(queries[B, d], nc[E, B]) →
        (scores[B, slots], entry[B, slots], doc[B, slots], counts[B, E])
    The merged stream comes back FULLY ordered (score desc, slot asc —
    slots = E_pad · kk) rather than cut at a global k, because the
    sequential coordinator's knn semantics cut at k PER SHARD before
    the global page: the collector walks the ordered stream applying
    per-shard rank caps, which a global top-k on device could starve
    (one dominant shard would evict other shards' in-page ranks).
    counts = surviving candidates PER ENTRY, for the per-shard totals
    (Σ_shards min(Σ_{entries∈shard} count, k)) of
    ops/scoring.knn_merge_segment_topk.
    """
    n_docs = int(vectors.shape[1])
    kk = min(kc, n_docs)

    def body(vectors_b, cand_b, queries, nc_b):
        q = queries
        if similarity == "cosine":
            qn = jnp.linalg.norm(q, axis=1, keepdims=True)
            q = q / jnp.where(qn == 0, 1.0, qn)

        def entry(vectors_e, cand_e):
            dots = q @ vectors_e.T  # [Bd, N] — MXU
            if similarity in ("cosine", "dot_product"):
                scores = (1.0 + dots) / 2.0
            elif similarity == "l2_norm":
                q2 = jnp.sum(q * q, axis=1, keepdims=True)
                v2 = jnp.sum(vectors_e * vectors_e, axis=1)[None, :]
                scores = 1.0 / (1.0 + jnp.maximum(q2 + v2 - 2.0 * dots, 0.0))
            elif similarity == "max_inner_product":
                scores = jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
            else:
                raise ValueError(f"unknown similarity [{similarity}]")
            scores = jnp.where(
                cand_e[None, :], scores.astype(jnp.float32), -jnp.inf
            )
            return jax.lax.top_k(scores, kk)

        s, d = jax.vmap(entry)(vectors_b, cand_b)  # [F, Bd, kk] ×2
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, kk]
        gd = jax.lax.all_gather(d, SHARD_AXIS)
        gn = jax.lax.all_gather(nc_b, SHARD_AXIS)  # [G, F, Bd]
        G, F, Bd, _ = gs.shape
        slots = G * F * kk
        gs2 = jnp.transpose(gs, (2, 0, 1, 3)).reshape(Bd, slots)
        gd2 = jnp.transpose(gd, (2, 0, 1, 3)).reshape(Bd, slots)
        nc2 = jnp.transpose(gn, (2, 0, 1)).reshape(Bd, G * F)
        entry_of_slot = jnp.arange(slots, dtype=jnp.int32) // kk
        rank_of_slot = jnp.arange(slots, dtype=jnp.int32) % kk
        nc_slot = jnp.take(nc2, entry_of_slot, axis=1)  # [Bd, slots]
        valid = jnp.isfinite(gs2) & (rank_of_slot[None, :] < nc_slot)
        masked = jnp.where(valid, gs2, -jnp.inf)
        ms, mi = jax.lax.top_k(masked, slots)
        me = entry_of_slot[mi]
        md = jnp.take_along_axis(gd2, mi, axis=1)
        counts = valid.reshape(Bd, G * F, kk).sum(axis=2, dtype=jnp.int32)
        return ms, me, md, counts

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None),
            P(DATA_AXIS, None),
            P(SHARD_AXIS, DATA_AXIS),
        ),
        out_specs=(
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
        ),
        check_vma=False,
    )

    @jax.jit
    def step(queries, nc):
        return fn(vectors, cand, queries, nc)

    return step


def build_mesh_ann_step(
    mesh: Mesh,
    centroids: jax.Array,  # f32 [E, nlist_max, d] (zero-padded entries)
    cvalid: jax.Array,  # bool [E, nlist_max] real clusters
    starts: jax.Array,  # i32 [E, nlist_max]
    counts: jax.Array,  # i32 [E, nlist_max]
    perm: jax.Array,  # i32 [E, Fmax] flat cluster-major slot → doc
    vecs: jax.Array,  # [E, Fmax, d] permuted block (f32/f16, or int8)
    scales: Optional[jax.Array],  # f32 [E, Fmax] int8 twin, or None
    v2: Optional[jax.Array],  # f32 [E, Fmax] (l2 only), or None
    cand: jax.Array,  # bool [E, Fmax] exists ∧ live in FLAT slot order
    similarity: str,
    nprobe: int,
    kc: int,
    cmax: int,
):
    """One SPMD IVF-probed kNN step: the centroid scan runs replicated
    per entry (each device scans only its own entries' centroids — tiny
    matmuls), cluster gathers stay device-local (clusters are sharded
    with their entries), and the merge is the SAME all_gather + per-
    (job, entry) num_candidates rank cut as build_mesh_knn_step, so the
    collector (MeshExecutor.collect_knn) is shared verbatim.

    fn(queries[B, d], nc[E, B]) →
        (scores[B, slots], entry[B, slots], doc[B, slots], counts[B, E])
    """
    from ..ops.ivf import QCHUNK, _similarity_transform

    kk = min(kc, nprobe * cmax)
    off = jnp.arange(cmax, dtype=jnp.int32)
    has_scales = scales is not None
    has_v2 = v2 is not None

    def body(cent_b, cv_b, st_b, ct_b, pm_b, vx_b, cd_b, queries, nc_b,
             *extra):
        ei = iter(extra)
        sc_b = next(ei) if has_scales else None
        v2_b = next(ei) if has_v2 else None
        q = queries
        if similarity == "cosine":
            qn = jnp.linalg.norm(q, axis=1, keepdims=True)
            q = q / jnp.where(qn == 0, 1.0, qn)

        def entry(args):
            cent_e, cv_e, st_e, ct_e, pm_e, vx_e, cd_e = args[:7]
            rest = args[7:]
            sc_e = rest[0] if has_scales else None
            v2_e = rest[-1] if has_v2 else None
            cdots = q @ cent_e.T  # [Bd, nlist_max]
            if similarity == "l2_norm":
                c2 = jnp.sum(cent_e * cent_e, axis=1)[None, :]
                csel = -(c2 - 2.0 * cdots)
            else:
                csel = cdots
            csel = jnp.where(cv_e[None, :], csel, -jnp.inf)
            p = min(nprobe, int(cent_e.shape[0]))
            _, cls = jax.lax.top_k(csel, p)  # [Bd, p]
            P_ = p * cmax

            def chunk(args):
                qc, clsc = args  # [C, d], [C, p]
                slot = (
                    jnp.take(st_e, clsc)[:, :, None] + off[None, None, :]
                ).reshape(qc.shape[0], P_)
                ok = (
                    off[None, None, :] < jnp.take(ct_e, clsc)[:, :, None]
                ).reshape(qc.shape[0], P_)
                docs = jnp.take(pm_e, slot)
                vv = jnp.take(vx_e, slot, axis=0).astype(jnp.float32)
                dots = jnp.einsum("cd,cpd->cp", qc, vv)
                if sc_e is not None:
                    dots = dots * jnp.take(sc_e, slot)
                if similarity == "l2_norm":
                    s = _similarity_transform(
                        dots, similarity, q=qc, v2=jnp.take(v2_e, slot)
                    )
                else:
                    s = _similarity_transform(dots, similarity)
                mask = ok & jnp.take(cd_e, slot)
                masked = jnp.where(mask, s.astype(jnp.float32), -jnp.inf)
                sk, ik = jax.lax.top_k(masked, min(kk, P_))
                dk = jnp.take_along_axis(docs, ik, axis=1)
                return sk, jnp.where(jnp.isfinite(sk), dk, 0)

            B = q.shape[0]
            C = min(QCHUNK, B)
            if B % C == 0 and B > C:
                sk, dk = jax.lax.map(
                    chunk,
                    (q.reshape(B // C, C, -1), cls.reshape(B // C, C, -1)),
                )
                sk = sk.reshape(B, -1)
                dk = dk.reshape(B, -1)
            else:
                sk, dk = chunk((q, cls))
            if sk.shape[1] < kk:  # P_ < kk: pad to the shared width
                padw = kk - sk.shape[1]
                sk = jnp.pad(sk, ((0, 0), (0, padw)),
                             constant_values=-jnp.inf)
                dk = jnp.pad(dk, ((0, 0), (0, padw)))
            return sk, dk

        ins = [cent_b, cv_b, st_b, ct_b, pm_b, vx_b, cd_b]
        if has_scales:
            ins.append(sc_b)
        if has_v2:
            ins.append(v2_b)
        s, d = jax.vmap(entry)(tuple(ins))  # [F, Bd, kk] ×2
        gs = jax.lax.all_gather(s, SHARD_AXIS)  # [G, F, Bd, kk]
        gd = jax.lax.all_gather(d, SHARD_AXIS)
        gn = jax.lax.all_gather(nc_b, SHARD_AXIS)  # [G, F, Bd]
        G, F, Bd, _ = gs.shape
        slots = G * F * kk
        gs2 = jnp.transpose(gs, (2, 0, 1, 3)).reshape(Bd, slots)
        gd2 = jnp.transpose(gd, (2, 0, 1, 3)).reshape(Bd, slots)
        nc2 = jnp.transpose(gn, (2, 0, 1)).reshape(Bd, G * F)
        entry_of_slot = jnp.arange(slots, dtype=jnp.int32) // kk
        rank_of_slot = jnp.arange(slots, dtype=jnp.int32) % kk
        nc_slot = jnp.take(nc2, entry_of_slot, axis=1)
        valid = jnp.isfinite(gs2) & (rank_of_slot[None, :] < nc_slot)
        masked = jnp.where(valid, gs2, -jnp.inf)
        ms, mi = jax.lax.top_k(masked, slots)
        me = entry_of_slot[mi]
        md = jnp.take_along_axis(gd2, mi, axis=1)
        cnt = valid.reshape(Bd, G * F, kk).sum(axis=2, dtype=jnp.int32)
        return ms, me, md, cnt

    sh2 = P(SHARD_AXIS, None)
    sh3 = P(SHARD_AXIS, None, None)
    in_specs = [sh3, sh2, sh2, sh2, sh2, sh3, sh2,
                P(DATA_AXIS, None), P(SHARD_AXIS, DATA_AXIS)]
    extras = []
    if has_scales:
        extras.append(scales)
        in_specs.append(sh2)
    if has_v2:
        extras.append(v2)
        in_specs.append(sh2)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS, None),
        ),
        check_vma=False,
    )

    @jax.jit
    def step(queries, nc):
        return fn(
            centroids, cvalid, starts, counts, perm, vecs, cand,
            queries, nc, *extras,
        )

    return step


def build_mesh_agg_step(
    mesh: Mesh,
    live: jax.Array,  # bool[E, Nmax] (live docs ∧ in-range padding mask)
    node_descs: Sequence[tuple],
    text: Optional[tuple],  # None | (doc_ids[E,T,128], tfs, inv[E,Nmax])
    with_cnt: bool,
):
    """One SPMD aggregation step over stacked (shard, segment) entries:
    per-entry bucket accumulators (segment-sum scatters over the stacked
    doc-value / ordinal columns) reduce across the ``shards`` axis with
    ``psum`` (counts, sums) / ``pmin`` / ``pmax`` — the coordinator's
    agg reduce collapsed onto the ICI, one launch for the whole index.

    ``node_descs`` (arrays stacked [E, …] and device-sharded on
    ``shards``): ("metric", values, exists) → per-row (count, sum, min,
    max); ("counts_doc", ids, exists, nbpad) → per-row int32[nbpad]
    bucket counts (histogram family — ids are host-precomputed exact
    relative bucket ids); ("counts_entry", gords, edocs, evalid, nbpad)
    → the same over the multi-value ordinal CSR mapped to a GLOBAL
    ordinal table (keyword terms — the table union happens host-side at
    snapshot build; the per-entry count vectors are what psum merges
    across the shards axis).

    ``text`` carries one match-plan field's stacked postings (the query
    mask is the same per-entry BM25 accumulation the serving text step
    runs — float-exact masks); None serves match_all (mask = live).

    fn(ti[E,B,T], tw, tv, msm[B]) →
        (totals[B], max_scores[B], per-node outputs…), everything
    replicated over ``shards`` and sharded over ``data`` only.
    """
    has_text = text is not None
    n_docs = int(live.shape[1])

    def body(*args):
        it = iter(args)
        if has_text:
            d_b = next(it)
            t_b = next(it)
            i_b = next(it)
        live_b = next(it)
        node_b = []
        for desc in node_descs:
            kind = desc[0]
            if kind == "metric":
                node_b.append((kind, next(it), next(it), next(it)))
            elif kind == "counts_doc":
                node_b.append((kind, next(it), next(it), desc[3]))
            else:  # counts_entry
                node_b.append((kind, next(it), next(it), next(it), desc[4]))
        ti_b = next(it)
        tw_b = next(it)
        tv_b = next(it)
        msm = next(it)
        Bd = msm.shape[0]

        def scatter_rows(ids_e, sel, nbpad):
            # [Bd, L] selection → [Bd, nbpad] counts; unselected slots
            # land in a trash bucket that psum never sees
            def one(sel_row):
                safe = jnp.where(sel_row, ids_e, nbpad)
                return (
                    jnp.zeros(nbpad + 1, jnp.int32).at[safe].add(1)[:nbpad]
                )

            return jax.vmap(one)(sel)

        def entry(e_args):
            it2 = iter(e_args)
            if has_text:
                dids = next(it2)
                tfs_ = next(it2)
                inv = next(it2)
            live_e = next(it2)
            nodes_e = []
            for desc in node_b:
                n_arr = len(desc) - (1 if desc[0] == "metric" else 2)
                arrs = tuple(next(it2) for _ in range(n_arr))
                nodes_e.append((desc[0], arrs, desc[-1]))
            ti_e = next(it2)
            tw_e = next(it2)
            tv_e = next(it2)
            if has_text:
                nt = dids.shape[0]
                rows_d = dids[jnp.clip(ti_e, 0, nt - 1)]
                rows_t = tfs_[jnp.clip(ti_e, 0, nt - 1)]
                valid = (rows_d >= 0) & tv_e[:, :, None]
                tgt, s = bm25_tile_contrib(
                    rows_d, rows_t, tw_e[:, :, None], valid, inv, n_docs
                )
                acc = jnp.zeros((Bd, n_docs + 1), jnp.float32)
                acc = jax.vmap(
                    lambda a, d2, v2: a.at[d2.ravel()].add(v2.ravel())
                )(acc, tgt, s)
                scores = acc[:, :n_docs]
                if with_cnt:
                    cnt = jnp.zeros((Bd, n_docs + 1), jnp.int32)
                    cnt = jax.vmap(
                        lambda c, d2, v2: c.at[d2.ravel()].add(
                            v2.ravel().astype(jnp.int32)
                        )
                    )(cnt, tgt, valid)
                    mask = cnt[:, :n_docs] >= jnp.maximum(msm, 1)[:, None]
                else:
                    mask = scores > 0
            else:
                mask = jnp.ones((Bd, n_docs), bool)
                scores = jnp.ones((Bd, n_docs), jnp.float32)
            mask = mask & live_e[None, :]
            total_e = mask.sum(axis=1, dtype=jnp.int32)
            max_e = jnp.where(mask, scores, -jnp.inf).max(axis=1)
            outs = []
            for kind, arrs, nbpad in nodes_e:
                if kind == "metric":
                    vals, ivals, exists = arrs
                    sel = mask & exists[None, :]
                    v = vals.astype(jnp.float32)
                    outs.append(
                        (
                            sel.sum(axis=1, dtype=jnp.int32),
                            jnp.where(sel, ivals, 0).sum(
                                axis=1, dtype=jnp.int32
                            ),
                            jnp.where(sel, v, jnp.inf).min(axis=1),
                            jnp.where(sel, v, -jnp.inf).max(axis=1),
                        )
                    )
                elif kind == "counts_doc":
                    ids_e, exists = arrs
                    sel = mask & exists[None, :]
                    outs.append(scatter_rows(ids_e, sel, nbpad))
                else:  # counts_entry
                    gords_e, edocs_e, evalid_e = arrs
                    sel = (
                        jnp.take(mask, edocs_e, axis=1)
                        & evalid_e[None, :]
                    )
                    outs.append(scatter_rows(gords_e, sel, nbpad))
            return (total_e, max_e, tuple(outs))

        per_entry = []
        if has_text:
            per_entry.extend([d_b, t_b, i_b])
        per_entry.append(live_b)
        for desc in node_b:
            per_entry.extend(desc[1:] if desc[0] == "metric" else desc[1:-1])
        per_entry.extend([ti_b, tw_b, tv_b])
        total_f, max_f, outs_f = jax.vmap(
            lambda *xs: entry(xs)
        )(*per_entry)
        totals = jax.lax.psum(
            total_f.sum(axis=0), SHARD_AXIS
        )
        maxs = jax.lax.pmax(max_f.max(axis=0), SHARD_AXIS)
        outs = []
        for desc, out_f in zip(node_descs, outs_f):
            if desc[0] == "metric":
                c_f, s_f, mn_f, mx_f = out_f
                outs.append(
                    (
                        jax.lax.psum(c_f.sum(axis=0), SHARD_AXIS),
                        jax.lax.psum(s_f.sum(axis=0), SHARD_AXIS),
                        jax.lax.pmin(mn_f.min(axis=0), SHARD_AXIS),
                        jax.lax.pmax(mx_f.max(axis=0), SHARD_AXIS),
                    )
                )
            else:
                outs.append(
                    jax.lax.psum(out_f.sum(axis=0), SHARD_AXIS)
                )
        return (totals, maxs) + tuple(
            x for o in outs for x in (o if isinstance(o, tuple) else (o,))
        )

    p3 = P(SHARD_AXIS, None, None)
    p2 = P(SHARD_AXIS, None)
    p_plan = P(SHARD_AXIS, DATA_AXIS, None)
    in_specs: list = []
    if has_text:
        in_specs.extend([p3, p3, p2])
    in_specs.append(p2)
    for desc in node_descs:
        in_specs.extend([p2] * (len(desc) - (2 if desc[0] != "metric" else 1)))
    in_specs.extend([p_plan, p_plan, p_plan, P(DATA_AXIS)])
    out_specs: list = [P(DATA_AXIS), P(DATA_AXIS)]
    for desc in node_descs:
        if desc[0] == "metric":
            out_specs.extend([P(DATA_AXIS)] * 4)
        else:
            out_specs.append(P(DATA_AXIS, None))
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )
    static_arrays: list = []
    if has_text:
        static_arrays.extend(list(text))
    static_arrays.append(live)
    for desc in node_descs:
        static_arrays.extend(desc[1:-1] if desc[0] != "metric" else desc[1:])

    @jax.jit
    def step(ti, tw, tv, msm):
        return fn(*static_arrays, ti, tw, tv, msm)

    return step
