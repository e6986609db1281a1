"""Late-interaction (maxsim) rescoring kernels.

Second-stage reranking on device (GPUSparse's lesson, PAPERS.md): the
fused first-stage top-k candidates already live in HBM at merge time,
so reranking costs ONE extra device step — gather each candidate's
token-embedding block from the flat per-shard `rank_vectors` column,
contract it against the query-token matrix on the MXU, take the
per-query-token max (the "late interaction"), sum, blend with the
first-stage score, and re-sort the rescore window — all before the one
packed download.

Layout contract (executor_jax.rerank_column / mesh `_rerank_view`):
token rows are flat `[Tflat, d]` with per-doc CSR bounds `starts[doc]`/
`counts[doc]`; the flat array carries `tmax` zero rows of tail padding
so `start + arange(tmax)` never reads out of bounds (the ops/ivf
cluster-gather trick). The int8 twin stores per-token symmetric scales
(`models/rerank.quantize_tokens`); the kernel computes
`(q · v_int8) · scale` in float32 — the exact float path the host
oracle `host_maxsim_quantized` reproduces.

Precision of a BYTE column (`element_type: byte`: int8 rows, no scales;
the bytes ARE the values): stated, not the einsum's default. The
gathered bytes are exact in bfloat16; the float32 query row is split
into three bfloat16 parts whose sum is the float32 value (`split_bf16`)
and the three are contracted against the bytes in ONE bfloat16 MXU
pass with float32 accumulation, then added in float32. Every product
is exact (8 x 8 significant bits) and only the float32 sums round: the
result is float32 of sum_i max_j q_i . d_j to a few ulps, where the
default precision would round the query to ONE bfloat16 part (4e-3
relative a component). Its temporaries are bounded whatever the
launch's rows: the rows are walked (`lax.map`), so a launch allocates
what ONE row needs (window bucket x tmax x d bytes gathered, twice
that in bfloat16, 3 x Qt x window x tmax float32 products: 23.6 MB +
47 MB + 71 MB at 1,024 x 180 x 128 and 32 query rows).

Ordering contract: the rescore window is re-sorted by blended score
desc with ties broken by FIRST-STAGE rank asc (lax.top_k is stable, so
equal blended scores keep their incoming order — candidates arrive
score desc, (segment, doc) asc). Candidates past the window keep their
first-stage score and order below the window (the QueryRescorer
window contract).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .scoring import _to_host


def rerank_flops(
    n_queries: int, n_qtoks: int, window: int, tmax: int, dims: int
) -> int:
    """Useful-flop estimate of one maxsim launch (the profile's)."""
    return 2 * n_queries * n_qtoks * window * tmax * dims


def maxsim_candidates(
    qtoks: jax.Array,  # f32 [B, Qt, d]
    qvalid: jax.Array,  # bool [B, Qt] (padded query-token rows)
    starts: jax.Array,  # i32 [N] doc → first flat token row
    counts: jax.Array,  # i32 [N] doc → token count
    toks: jax.Array,  # [Tflat, d] f32, or int8 when scales given
    scales: Optional[jax.Array],  # f32 [Tflat] (int8 twin) or None
    docs: jax.Array,  # i32 [B, W] candidate doc ids (clipped >= 0)
    tmax: int,
) -> jax.Array:
    """Raw maxsim per candidate, f32 [B, W]; docs without tokens score
    0.0. Plain traceable function — shared by the jitted single-device
    wrapper below and the mesh SPMD step (parallel/sharded)."""
    d = jnp.clip(docs, 0, starts.shape[0] - 1)
    st = jnp.take(starts, d)  # [B, W]
    ct = jnp.take(counts, d)
    if toks.dtype == jnp.int8 and scales is None:
        # a byte column: exact products at the stated precision, one
        # query row's temporaries at a time
        def one(args):
            return _maxsim_bytes_row(*args, toks, tmax)

        if qtoks.shape[0] == 1:
            return one((qtoks[0], qvalid[0], st[0], ct[0]))[None]
        return jax.lax.map(one, (qtoks, qvalid, st, ct))
    off = jnp.arange(tmax, dtype=jnp.int32)
    slot = st[:, :, None] + off[None, None, :]  # [B, W, T]
    slot = jnp.clip(slot, 0, toks.shape[0] - 1)
    tok_ok = off[None, None, :] < ct[:, :, None]  # [B, W, T]
    tv = jnp.take(toks, slot, axis=0).astype(jnp.float32)  # [B, W, T, d]
    dots = jnp.einsum("bqd,bwtd->bqwt", qtoks, tv)  # MXU contraction
    if scales is not None:
        dots = dots * jnp.take(scales, slot)[:, None, :, :]
    dots = jnp.where(tok_ok[:, None, :, :], dots, -jnp.inf)
    per_q = dots.max(axis=3)  # [B, Qt, W] max over doc tokens
    # token-less docs: every slot masked → -inf → contribute 0.0
    per_q = jnp.where(jnp.isfinite(per_q), per_q, 0.0)
    per_q = jnp.where(qvalid[:, :, None], per_q, 0.0)
    return per_q.sum(axis=1)  # [B, W]


def split_bf16(x: jax.Array) -> jax.Array:
    """f32 [..., d] -> bf16 [3, ..., d] whose float32 sum is `x`: the
    leading 8 significant bits, the next 8 and the last 8."""
    def head(v):
        # rounds to bfloat16's 8 significant bits and STAYS float32: a
        # convert there and back is what a compiler may take out
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = head(x)
    mid = head(x - hi)
    lo = head((x - hi) - mid)
    return jnp.stack([hi, mid, lo]).astype(jnp.bfloat16)  # exact casts


def _maxsim_bytes_row(q, qv, st, ct, toks, tmax: int) -> jax.Array:
    """One query row against its candidates' byte rows: q f32 [Qt, d],
    qv bool [Qt], st / ct i32 [W], toks int8 [Tflat, d] -> f32 [W]."""
    qt = q.shape[0]
    off = jnp.arange(tmax, dtype=jnp.int32)
    slot = jnp.clip(st[:, None] + off[None, :], 0, toks.shape[0] - 1)
    tok_ok = off[None, :] < ct[:, None]  # [W, T]
    tv = jnp.take(toks, slot, axis=0).astype(jnp.bfloat16)  # exact
    parts = jnp.einsum(
        "qd,wtd->qwt", split_bf16(q).reshape(3 * qt, -1), tv,
        preferred_element_type=jnp.float32,
    )  # [3 Qt, W, T]: exact products, float32 sums
    dots = (parts[:qt] + parts[qt : 2 * qt]) + parts[2 * qt :]
    dots = jnp.where(tok_ok[None, :, :], dots, -jnp.inf)
    per_q = dots.max(axis=2)  # [Qt, W]
    per_q = jnp.where(jnp.isfinite(per_q), per_q, 0.0)
    per_q = jnp.where(qv[:, None], per_q, 0.0)
    return per_q.sum(axis=0)


def blend_and_sort(
    msim: jax.Array,  # f32 [B, W] raw maxsim
    first: jax.Array,  # f32 [B, W] first-stage scores (score desc)
    valid: jax.Array,  # bool [B, W] real candidates
    weights: jax.Array,  # f32 [2] (query_weight, rescore_query_weight)
    window: int,
) -> Tuple[jax.Array, jax.Array]:
    """(scores [B, W], perm [B, W]): positions < window re-sorted by
    blended = qw·first + rw·maxsim (desc, stable → first-stage rank
    breaks ties); the tail keeps first-stage scores and order."""
    w = min(window, int(first.shape[1]))
    blended = weights[0] * first + weights[1] * msim
    blended = jnp.where(valid, blended, -jnp.inf)
    ws, wi = jax.lax.top_k(blended[:, :w], w)
    perm = jnp.concatenate(
        [
            wi.astype(jnp.int32),
            jnp.broadcast_to(
                jnp.arange(w, first.shape[1], dtype=jnp.int32)[None, :],
                (first.shape[0], first.shape[1] - w),
            ),
        ],
        axis=1,
    )
    tail = jnp.where(valid[:, w:], first[:, w:], -jnp.inf)
    scores = jnp.concatenate([ws, tail], axis=1)
    return scores, perm


@functools.partial(jax.jit, static_argnames=("tmax", "window"))
def _maxsim_rescore(
    qtoks, qvalid, starts, counts, toks, scales, docs, first, valid,
    weights, tmax: int, window: int,
):
    msim = maxsim_candidates(
        qtoks, qvalid, starts, counts, toks, scales, docs, tmax
    )
    scores, perm = blend_and_sort(msim, first, valid, weights, window)
    # one packed buffer: bitcast scores next to the int32 permutation
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(scores, jnp.int32), perm], axis=1
    )


def maxsim_rescore_batch(
    qtoks: np.ndarray,  # f32 [B, Qt, d] (padded rows zero)
    qvalid: np.ndarray,  # bool [B, Qt]
    starts: jax.Array,
    counts: jax.Array,
    toks: jax.Array,
    scales: Optional[jax.Array],
    docs: np.ndarray,  # i32 [B, W]
    first: np.ndarray,  # f32 [B, W]
    valid: np.ndarray,  # bool [B, W]
    query_weight: float,
    rescore_query_weight: float,
    tmax: int,
    window: int,
) -> jax.Array:
    """Launches the maxsim+blend+sort kernel; returns the DEVICE packed
    [B, 2W] buffer (zero host syncs — `unpack_rescore` performs the one
    packed download at collect time)."""
    return _maxsim_rescore(
        jnp.asarray(np.asarray(qtoks, np.float32)),
        jnp.asarray(np.asarray(qvalid, bool)),
        starts,
        counts,
        toks,
        scales,
        jnp.asarray(np.asarray(docs, np.int32)),
        jnp.asarray(np.asarray(first, np.float32)),
        jnp.asarray(np.asarray(valid, bool)),
        jnp.asarray(
            np.asarray([query_weight, rescore_query_weight], np.float32)
        ),
        tmax=int(tmax),
        window=int(window),
    )


def unpack_rescore(packed) -> Tuple[np.ndarray, np.ndarray]:
    """The ONE packed download: (scores f32 [B, W], perm i32 [B, W])."""
    out = _to_host(packed)  # noted: `transfer.scoring`, a `download` span
    w = out.shape[1] // 2
    scores = out[:, :w].copy().view(np.float32)
    perm = out[:, w:]
    return scores, perm
