"""IVF (inverted-file) clustered ANN: device k-means build + probed search.

Reference analog: Lucene's move from brute-force vector scans toward ANN
(the Lucene ANN paper, PAPERS.md arXiv:1910.10208, frames the
recall/latency tradeoff) and FAISS's IndexIVFFlat layout. The TPU-shaped
formulation:

* **Build** (refresh/merge time, per segment): plain-`jnp` Lloyd
  iterations — a fixed number of (assign → segment-sum → divide) steps,
  seeded host-side init, no convergence check — so the build is
  deterministic for a given (vectors, nlist, seed) on any backend. The
  final assignment induces a CLUSTER-MAJOR permutation of the vector
  block (and of its int8-quantized twin): each cluster's vectors are
  contiguous rows, so probing a cluster is a contiguous gather, not a
  scatter of random rows.
* **Search**: score the query against the centroids (one small matmul),
  pick the top-`nprobe` clusters, gather only those clusters' rows from
  the permuted block, score them with the SAME similarity transform as
  the exact kernels (ops/scoring.knn_scores), and top-k the gathered
  candidates. Query rows are chunked through `lax.map` so the gathered
  [chunk, nprobe·cmax, d] block bounds peak memory regardless of the
  launch's row bucket.

The exact brute-force path stays the float oracle forever; callers fall
back to it for small segments, HBM pressure, or any probe-path failure.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# queries scored per lax.map step: bounds the gathered candidate block
# ([QCHUNK, nprobe*cmax, d] floats) independently of the row bucket
QCHUNK = 8
# fixed Lloyd iteration count (no convergence check → deterministic)
KMEANS_ITERS = 8


# ---------------------------------------------------------------------------
# k-means build (device Lloyd iterations, seeded + deterministic)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(1,))
def _lloyd_step(vecs: jax.Array, cents: jax.Array) -> jax.Array:
    """One Lloyd iteration: squared-L2 assignment + segment-sum update.
    Empty clusters keep their previous centroid (deterministic, no
    re-seeding)."""
    # argmin_c |v|² - 2 v·c + |c|² == argmin_c |c|² - 2 v·c
    dots = vecs @ cents.T  # [N, C] — the MXU contraction
    c2 = jnp.sum(cents * cents, axis=1)[None, :]
    assign = jnp.argmin(c2 - 2.0 * dots, axis=1)
    nlist = cents.shape[0]
    sums = jnp.zeros_like(cents).at[assign].add(vecs)
    counts = jnp.zeros(nlist, jnp.float32).at[assign].add(1.0)
    return jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cents
    )


@jax.jit
def _assign(vecs: jax.Array, cents: jax.Array) -> jax.Array:
    dots = vecs @ cents.T
    c2 = jnp.sum(cents * cents, axis=1)[None, :]
    return jnp.argmin(c2 - 2.0 * dots, axis=1).astype(jnp.int32)


def _two_means(pts: np.ndarray, seed: int, iters: int = 6):
    """Deterministic host 2-means over one oversized cluster's members:
    (centroids f32[2, d], assign i32[m])."""
    m = len(pts)
    rng = np.random.default_rng(seed)
    i0, i1 = np.sort(rng.choice(m, size=2, replace=False))
    c = np.stack([pts[i0], pts[i1]]).astype(np.float32)
    a = np.zeros(m, np.int64)
    for _ in range(iters):
        d0 = ((pts - c[0]) ** 2).sum(axis=1)
        d1 = ((pts - c[1]) ** 2).sum(axis=1)
        a = (d1 < d0).astype(np.int64)
        for j in (0, 1):
            sel = a == j
            if sel.any():
                c[j] = pts[sel].mean(axis=0)
    return c, a


def kmeans(
    vectors: np.ndarray, nlist: int, seed: int, iters: int = KMEANS_ITERS
) -> Tuple[np.ndarray, np.ndarray]:
    """(centroids f32[C, d], assign i32[N]) — seeded host init + `iters`
    device Lloyd steps, then oversized clusters split in two (2-means)
    until the largest is within ~1.5x the mean. Deterministic across
    runs: host RNG init, fixed iteration counts, size-ordered splits.

    The balancing matters as much as the clustering: the probe kernel's
    cost is nprobe × cmax (every probed cluster pays the LARGEST
    cluster's padded width), so an imbalanced build would hand back the
    latency the probing saved. C can exceed the requested nlist by the
    number of splits (bounded at 2x)."""
    v = np.ascontiguousarray(vectors, dtype=np.float32)
    n = v.shape[0]
    nlist = max(1, min(int(nlist), n))
    rng = np.random.default_rng(seed)
    init = rng.choice(n, size=nlist, replace=False)
    init.sort()  # choice order is generator-dependent detail; sort it away
    cents = jnp.asarray(v[init])
    dv = jnp.asarray(v)
    for _ in range(max(1, int(iters))):
        cents = _lloyd_step(dv, cents)
    assign = np.asarray(_assign(dv, cents)).astype(np.int64)
    cents = np.asarray(cents)
    if nlist > 1:
        cap = max(32, int(np.ceil(1.5 * n / nlist)))
        counts = np.bincount(assign, minlength=nlist).astype(np.int64)
        cent_list = list(cents)
        max_c = 2 * nlist
        while counts.max() > cap and len(cent_list) < max_c:
            c = int(counts.argmax())
            members = np.nonzero(assign == c)[0]
            sub_c, sub_a = _two_means(
                v[members], seed ^ (0x9E3779B9 + len(cent_list))
            )
            if not sub_a.any() or sub_a.all():
                break  # degenerate (duplicate points): give up splitting
            new_id = len(cent_list)
            cent_list[c] = sub_c[0]
            cent_list.append(sub_c[1])
            assign[members[sub_a == 1]] = new_id
            counts = np.bincount(
                assign, minlength=len(cent_list)
            ).astype(np.int64)
        cents = np.stack(cent_list).astype(np.float32)
    return cents, assign.astype(np.int32)


# ---------------------------------------------------------------------------
# the per-segment index: cluster-major layout + device arrays
# ---------------------------------------------------------------------------


class IvfSegmentIndex:
    """Device-resident IVF index over one segment's vector column.

    Flat cluster-major layout: `perm[slot] → original doc`, cluster c
    owns slots [starts[c], starts[c]+counts[c]); the flat arrays carry
    `cmax` rows of padding at the tail so `starts[c] + arange(cmax)`
    never reads out of bounds (padded slots are masked by the
    rank < counts test). The int8 twin is a symmetric per-vector
    quantization (q = rint(v / scale), scale = max|v| / 127) so
    `index.knn.quantization: int8` probes read 4x less HBM."""

    def __init__(
        self,
        vectors: np.ndarray,  # similarity-prepared (unit rows for cosine)
        similarity: str,
        nlist: int,
        seed: int,
        quantized: bool = False,
    ):
        t0 = time.perf_counter()
        self.similarity = similarity
        self.n = int(vectors.shape[0])
        self.dims = int(vectors.shape[1])
        cents, assign = kmeans(vectors, nlist, seed)
        self.nlist = int(cents.shape[0])
        counts = np.bincount(assign, minlength=self.nlist).astype(np.int32)
        starts = np.zeros(self.nlist, np.int32)
        np.cumsum(counts[:-1], out=starts[1:])
        perm = np.argsort(assign, kind="stable").astype(np.int32)
        self.cmax = int(counts.max()) if self.n else 1
        pad = self.cmax
        perm_flat = np.concatenate([perm, np.zeros(pad, np.int32)])
        vecs_flat = np.concatenate(
            [vectors[perm], np.zeros((pad, self.dims), vectors.dtype)]
        )
        self.centroids = jnp.asarray(cents)
        self.starts = jnp.asarray(starts)
        self.counts = jnp.asarray(counts)
        self.perm = jnp.asarray(perm_flat)
        self.vecs_flat = jnp.asarray(vecs_flat)
        self.v2_flat = None
        if similarity == "l2_norm":
            v2 = np.sum(
                vecs_flat.astype(np.float32) * vecs_flat.astype(np.float32),
                axis=1,
            ).astype(np.float32)
            self.v2_flat = jnp.asarray(v2)
        self.qvecs_flat = None
        self.scales_flat = None
        self.host_qvecs_flat = None
        self.host_scales_flat = None
        if quantized:
            # symmetric per-vector int8, no lane padding: the probe
            # gather is a plain XLA einsum (models/rerank.quantize_tokens
            # is the same scheme a token)
            vf32 = vecs_flat.astype(np.float32)
            maxabs = np.abs(vf32).max(axis=1)
            scales = (maxabs / 127.0).astype(np.float32)
            safe = np.where(scales == 0, 1.0, scales)
            qv = (
                np.rint(vf32 / safe[:, None]).clip(-127, 127).astype(np.int8)
            )
            self.host_qvecs_flat = qv
            self.host_scales_flat = scales
            self.qvecs_flat = jnp.asarray(qv)
            self.scales_flat = jnp.asarray(scales)
        self.nbytes = int(
            cents.nbytes
            + starts.nbytes
            + counts.nbytes
            + perm_flat.nbytes
            + vecs_flat.nbytes
            + (self.v2_flat.nbytes if self.v2_flat is not None else 0)
            + (self.qvecs_flat.nbytes if self.qvecs_flat is not None else 0)
            + (
                self.scales_flat.nbytes
                if self.scales_flat is not None
                else 0
            )
        )
        self.build_ms = (time.perf_counter() - t0) * 1000.0
        # host copies for the mesh executor's stacked ANN view
        self.host_centroids = cents
        self.host_starts = starts
        self.host_counts = counts
        self.host_perm = perm_flat
        self.host_vecs_flat = vecs_flat

    @staticmethod
    def estimate_nbytes(
        n: int, dims: int, nlist: int, quantized: bool, itemsize: int = 4
    ) -> int:
        """Pre-build HBM estimate for the ledger breaker precheck."""
        flat = n + max(1, n // max(1, nlist)) * 2
        base = nlist * dims * 4 + nlist * 8 + flat * 4 + flat * dims * itemsize
        if quantized:
            base += flat * dims + flat * 4
        return base


def auto_nlist(n: int) -> int:
    """Default cluster count: ~2·sqrt(N) (the FAISS-guideline range),
    bounded so clusters average at least 16 vectors. Probe cost scales
    with nprobe × (N / nlist), so the larger default halves the scanned
    rows vs plain sqrt(N) at the same measured recall on clustered
    corpora."""
    return max(1, min(2 * int(round(np.sqrt(max(n, 1)))), max(1, n // 16)))


def ann_flops(n_queries: int, nlist: int, nprobe: int, cmax: int, dims: int) -> int:
    """Useful-flop estimate of one probed search (the profile's): the
    centroid scan plus the gathered-candidate contraction."""
    scanned = nlist + nprobe * cmax
    return 2 * n_queries * scanned * dims


# ---------------------------------------------------------------------------
# probed search kernel
# ---------------------------------------------------------------------------


def _similarity_transform(dots, similarity, q=None, v2=None):
    if similarity in ("cosine", "dot_product"):
        return (1.0 + dots) / 2.0
    if similarity == "max_inner_product":
        return jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
    if similarity == "l2_norm":
        q2 = jnp.sum(q * q, axis=1, keepdims=True)
        d2 = jnp.maximum(q2 + v2 - 2.0 * dots, 0.0)
        return 1.0 / (1.0 + d2)
    raise ValueError(f"unknown similarity [{similarity}]")


@functools.partial(
    jax.jit,
    static_argnames=("similarity", "nprobe", "k", "cmax", "qchunk"),
)
def _ivf_probe_topk(
    queries: jax.Array,  # f32 [B, d]
    valid: jax.Array,  # bool [B]
    centroids: jax.Array,  # f32 [nlist, d]
    starts: jax.Array,  # i32 [nlist]
    counts: jax.Array,  # i32 [nlist]
    perm: jax.Array,  # i32 [Nflat]
    vecs: jax.Array,  # [Nflat, d] (f32/f16) OR int8 when scales given
    scales: Optional[jax.Array],  # f32 [Nflat] (int8 twin) or None
    v2: Optional[jax.Array],  # f32 [Nflat] (l2 only) or None
    cand: Optional[jax.Array],  # bool [N] original-doc order, or None
    similarity: str,
    nprobe: int,
    k: int,
    cmax: int,
    qchunk: int,
) -> Tuple[jax.Array, jax.Array]:
    q = queries
    if similarity == "cosine":
        qn = jnp.linalg.norm(q, axis=1, keepdims=True)
        q = q / jnp.where(qn == 0, 1.0, qn)
    # centroid scan (replicated, tiny): transformed scores are monotonic
    # in the raw metric, so top-nprobe selection matches either way
    cdots = q @ centroids.T  # [B, nlist]
    if similarity == "l2_norm":
        c2 = jnp.sum(centroids * centroids, axis=1)[None, :]
        csel = -(c2 - 2.0 * cdots)
    else:
        csel = cdots
    _, cls = jax.lax.top_k(csel, min(nprobe, centroids.shape[0]))  # [B, p]
    # permute the candidate mask into cluster-major order once
    if cand is not None:
        cand_flat = jnp.take(cand, jnp.clip(perm, 0, cand.shape[0] - 1))
    else:
        cand_flat = None
    P = cls.shape[1] * cmax
    off = jnp.arange(cmax, dtype=jnp.int32)

    def chunk(args):
        qc, clsc, vc = args  # [C, d], [C, p], [C]
        slot = (
            jnp.take(starts, clsc)[:, :, None] + off[None, None, :]
        ).reshape(qc.shape[0], P)
        ok = (
            off[None, None, :] < jnp.take(counts, clsc)[:, :, None]
        ).reshape(qc.shape[0], P)
        docs = jnp.take(perm, slot)  # [C, P]
        vv = jnp.take(vecs, slot, axis=0).astype(jnp.float32)  # [C, P, d]
        dots = jnp.einsum("cd,cpd->cp", qc, vv)
        if scales is not None:
            dots = dots * jnp.take(scales, slot)
        if similarity == "l2_norm":
            sc = _similarity_transform(
                dots, similarity, q=qc, v2=jnp.take(v2, slot)
            )
        else:
            sc = _similarity_transform(dots, similarity)
        mask = ok & vc[:, None]
        if cand_flat is not None:
            mask = mask & jnp.take(cand_flat, slot)
        masked = jnp.where(mask, sc.astype(jnp.float32), -jnp.inf)
        s, i = jax.lax.top_k(masked, min(k, P))
        d = jnp.take_along_axis(docs, i, axis=1)
        return s, jnp.where(jnp.isfinite(s), d, 0)

    B = q.shape[0]
    C = min(qchunk, B)
    if B % C == 0 and B > C:
        s, d = jax.lax.map(
            chunk,
            (
                q.reshape(B // C, C, -1),
                cls.reshape(B // C, C, -1),
                valid.reshape(B // C, C),
            ),
        )
        return s.reshape(B, -1), d.reshape(B, -1)
    return chunk((q, cls, valid))


def ann_topk_batch(
    index: IvfSegmentIndex,
    queries: np.ndarray,  # f32 [B, d]
    valid: np.ndarray,  # bool [B]
    cand,  # bool [N] device/host array (exists ∧ live ∧ filter), or None
    nprobe: int,
    k: int,
    quantized: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(scores[B, k'], docs[B, k']) DEVICE arrays over the probed
    clusters, k' = min(k, nprobe·cmax); -inf rows pad short results.
    Same zero-sync contract as scoring.knn_topk_batch — the buffers
    feed knn_merge_segment_topk without a host round trip."""
    nprobe = max(1, min(int(nprobe), index.nlist))
    use_quant = quantized and index.qvecs_flat is not None
    return _ivf_probe_topk(
        jnp.asarray(np.asarray(queries, np.float32)),
        jnp.asarray(np.asarray(valid, bool)),
        index.centroids,
        index.starts,
        index.counts,
        index.perm,
        index.qvecs_flat if use_quant else index.vecs_flat,
        index.scales_flat if use_quant else None,
        index.v2_flat,
        None if cand is None else jnp.asarray(cand),
        similarity=index.similarity,
        nprobe=nprobe,
        k=int(k),
        cmax=index.cmax,
        qchunk=QCHUNK,
    )
