"""Device-side aggregation kernels: segment-sum / scatter-add bucket
accumulators over the doc-value and ordinal columns the JaxExecutor
already keeps device-resident.

Reference analog: org.elasticsearch.search.aggregations runs a
doc-at-a-time Collector per bucket; GPUSparse (PAPERS.md) shows the
accelerator-native reformulation this module implements — bucket
accumulation is a massively parallel scatter (``x.at[ids].add``, XLA's
segment-sum) over a dense per-doc bucket-id column, so a whole agg tree
costs a handful of kernel launches instead of a per-document host loop.

The bucket accumulators use the SORTED segment-sum formulation: a
host-precomputed bucket-major permutation + boundary array (cached per
column — query-independent) turns per-bucket reduction into gather →
cumsum → boundary-diff, which XLA executes fast on CPU and TPU alike
(naive scatter-adds serialize on the CPU backend).

Shapes and dtypes (the exactness contract — see search/aggs_device.py
for the routing predicate that enforces it):

  * bucket COUNTS are int32 cumulative sums — always exact.
  * metric SUMS accumulate as int32 cumulative sums over a host-
    prepared int32 copy of the column; routed to the device only when
    the column is integer-valued with Σ|v| inside the int32 window, so
    every partial sum is exact in ANY association order and equals the
    host oracle's float64 sum bit-for-bit.
  * MIN/MAX read float32 values at exact rank positions; routed only
    for f32-exact columns.
  * every kernel takes the query-match ``mask`` plus pre-permuted
    static gates (field exists), so the per-request work is a handful
    of vectorized primitives over the already-sorted layout.

(The mesh SPMD agg step in parallel/sharded.py keeps the plain
scatter-add formulation — its per-entry accumulators psum across the
shards axis and the TPU scatter unit handles them natively.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def sorted_bucket_counts(mask, map_perm, gate_perm, bounds):
    """int32[nb] per-bucket doc/entry counts via the SORTED segment-sum
    formulation: ``map_perm`` is a host-precomputed permutation that
    orders slots bucket-major (composed with the ordinal CSR's
    entry→doc map for keyword terms), ``gate_perm`` the pre-permuted
    static inclusion gate (field exists), ``bounds`` the int32[nb+1]
    bucket boundaries in the sorted order. Per-bucket counts are then
    boundary differences of one cumulative sum — gather + cumsum +
    diff, the formulation that is fast on BOTH the accelerator and the
    XLA CPU backend (a 200k-element scatter-add costs ~8 ms on XLA CPU
    vs ~0.5 ms for this pipeline; on the MXU/VPU both are cheap)."""
    selp = jnp.take(mask, map_perm) & gate_perm
    cs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(selp.astype(jnp.int32))]
    )
    return cs[bounds[1:]] - cs[bounds[:-1]]


@jax.jit
def sorted_bucket_metrics(mask, map_perm, gate_perm, v_perm, iv_perm,
                          bounds):
    """Per-bucket (count, int32 sum, min, max) — the bucket-id × metric
    segment_sum of one sub-agg level, in the sorted formulation.

    The permutation orders slots by (bucket, metric value asc), so a
    bucket's min/max are its FIRST/LAST selected slots: with the
    selection cumsum ``cs``, the k-th selected slot overall sits at
    ``searchsorted(cs, k)``, giving exact per-bucket extrema without a
    scatter. Sums ride the same cumsum trick over the exact int32 value
    copy (callers gate on the Σ|v| window)."""
    n = map_perm.shape[0]
    selp = jnp.take(mask, map_perm) & gate_perm
    cs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(selp.astype(jnp.int32))]
    )
    csum = jnp.concatenate(
        [
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(jnp.where(selp, iv_perm, 0)),
        ]
    )
    cnt = cs[bounds[1:]] - cs[bounds[:-1]]
    sm = csum[bounds[1:]] - csum[bounds[:-1]]
    ranks = cs[1:]
    fi = jnp.searchsorted(ranks, cs[bounds[:-1]] + 1)
    li = jnp.searchsorted(ranks, cs[bounds[1:]])
    mn = jnp.where(
        cnt > 0, v_perm[jnp.clip(fi, 0, n - 1)], jnp.inf
    )
    mx = jnp.where(
        cnt > 0, v_perm[jnp.clip(li, 0, n - 1)], -jnp.inf
    )
    return cnt, sm, mn, mx


@jax.jit
def masked_metric(sel, values, ivalues):
    """(count, int32 sum, min, max) of one metric leaf over the
    selected docs — a bucket_metrics with a single implicit bucket."""
    v = values.astype(jnp.float32)
    return (
        sel.sum(dtype=jnp.int32),
        jnp.where(sel, ivalues, 0).sum(dtype=jnp.int32),
        jnp.where(sel, v, jnp.inf).min(),
        jnp.where(sel, v, -jnp.inf).max(),
    )


@jax.jit
def masked_sorted(sel, values):
    """(ascending sorted selected values padded with +inf, count) — the
    sorted-quantile operand for percentiles. The host slices the first
    ``count`` entries after download."""
    v = jnp.where(sel, values.astype(jnp.float32), jnp.inf)
    return jnp.sort(v), sel.sum(dtype=jnp.int32)


@jax.jit
def wide_range_mask(hi_w, lo_w, exists, lhi, llo, hhi, hlo):
    """Range membership over a TWO-WORD integer column: the host splits
    value − column_min into (hi, lo) = divmod(Δ, 2**24) int32 words
    (exact for |Δ| < 2**53 — any date-millis span), and each bound into
    the same words, so [lo, hi) membership is a lexicographic int32
    compare — exact where a float32 column would mis-bucket."""
    ge = (hi_w > lhi) | ((hi_w == lhi) & (lo_w >= llo))
    lt = (hi_w < hhi) | ((hi_w == hhi) & (lo_w < hlo))
    return exists & ge & lt


@jax.jit
def masked_total_and_max(mask, scores):
    """(match count, max score) of one segment — the size:0 response's
    total/max_score without downloading an [n_docs] mask."""
    return (
        mask.sum(dtype=jnp.int32),
        jnp.where(mask, scores, -jnp.inf).max(),
    )


def agg_flops(n_slots: int, n_outputs: int) -> int:
    """Rough useful-work estimate for the profile breakdown: every slot
    is read once per output accumulator plus the mask combine."""
    return int(n_slots) * (2 + 3 * max(int(n_outputs), 1))
