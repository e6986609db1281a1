"""Pallas TPU kernel: int8-quantized brute-force vector scoring.

Reference analog: libs/simdvec (SURVEY.md §2.5) — Elasticsearch's only
hand-written SIMD kernels are int7/int8 dot-product and square-distance
over quantized vectors (NEON/SVE/AVX in libs/simdvec/native/vec.c),
used so HNSW scoring reads 4x less memory. The TPU equivalent keeps the
corpus int8 in HBM and dequantizes on-chip: the kernel streams doc
blocks HBM→VMEM (int8, so 4x the effective bandwidth of f32), promotes
to f32 in VMEM, runs the (B×d)·(d×N_blk) contraction on the MXU with
f32 accumulation, and applies per-vector scales to the product — the
scale multiply rides the same VPU pass that writes the block out.

Quantization: symmetric per-vector int8 (scale = max|v| / 127), the
moral equivalent of Lucene's int8_hnsw confidence-interval scheme
(Lucene99ScalarQuantizedVectorsFormat) minus the percentile clipping.

Compiled by Mosaic unless the caller passes `interpret=True` (the
CPU tests do, explicitly): no code here picks interpret mode from the
backend it finds. The per-vector scales ride as a 2-D `[1, N]` row: a
1-D f32 operand gets XLA's `T(1024)` tiling, which a 512-doc block
cannot agree with (the v5e compiler refuses it), while a `(1, 512)`
block of a 2-D row tiles cleanly; tests/test_chip_compile.py keeps
that compile as a test.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DOC_BLOCK = 512  # docs per grid step; int8 block (512, d) stays well under VMEM


def quantize_int8(vectors: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-vector int8: returns (q[int8, N, d_pad], scales[f32, N]).

    d is padded up to a lane multiple (128) so blocks tile cleanly; the
    zero padding contributes nothing to dot products.
    """
    n, d = vectors.shape
    d_pad = -(-d // LANE) * LANE
    maxabs = np.abs(vectors).max(axis=1)
    scales = (maxabs / 127.0).astype(np.float32)
    safe = np.where(scales == 0, 1.0, scales)
    q = np.rint(vectors / safe[:, None]).clip(-127, 127).astype(np.int8)
    if d_pad != d:
        q = np.pad(q, ((0, 0), (0, d_pad - d)))
    return q, scales


def _score_kernel(q_ref, qv_ref, scale_ref, out_ref):
    # qv block: [DOC_BLOCK, d] int8 → f32 on the VPU, contract on the MXU
    qv = qv_ref[:].astype(jnp.float32)
    dots = jax.lax.dot_general(
        q_ref[:],
        qv,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, DOC_BLOCK]
    out_ref[:] = dots * scale_ref[:]  # [1, DOC_BLOCK] row broadcasts over B


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_dot_scores(
    queries: jax.Array,  # f32 [B, d_pad]
    qvecs: jax.Array,  # int8 [N_pad, d_pad], N_pad % DOC_BLOCK == 0
    scales: jax.Array,  # f32 [N_pad] (or already [1, N_pad])
    interpret: bool = False,
) -> jax.Array:
    """Dequantized dot products [B, N_pad] via the Pallas kernel."""
    B, d = queries.shape
    N = qvecs.shape[0]
    grid = (N // DOC_BLOCK,)
    return pl.pallas_call(
        _score_kernel,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (DOC_BLOCK, d), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, DOC_BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec((B, DOC_BLOCK), lambda i: (0, i)),
        interpret=interpret,
    )(queries, qvecs, scales.reshape(1, N))


class QuantizedVectors:
    """Device-resident int8 corpus + the top-k search entry point."""

    def __init__(self, vectors: np.ndarray, similarity: str = "cosine"):
        self.similarity = similarity
        self.n, self.dims = vectors.shape
        mat = vectors
        if similarity == "cosine":
            norms = np.linalg.norm(mat, axis=1, keepdims=True)
            mat = (mat / np.where(norms == 0, 1.0, norms)).astype(np.float32)
        q, scales = quantize_int8(mat)
        self.n_pad = -(-self.n // DOC_BLOCK) * DOC_BLOCK
        if self.n_pad != self.n:
            q = np.pad(q, ((0, self.n_pad - self.n), (0, 0)))
            scales = np.pad(scales, (0, self.n_pad - self.n))
        self.d_pad = q.shape[1]
        self.qvecs = jnp.asarray(q)
        self.scales = jnp.asarray(scales).reshape(1, -1)

    def search(
        self, queries: np.ndarray, k: int, interpret: bool = False
    ) -> Tuple[jax.Array, jax.Array]:
        """(scores[B,k], docs[B,k]) with the similarity score transform
        applied (models/similarity.py mapping, same as the f32 path).

        Zero-sync contract (serving pipeline): the returned pair are
        DEVICE arrays from an async dispatch — no host transfer happens
        here, so a batcher collect stage can feed them straight into
        ops/scoring.knn_merge_segment_topk alongside the f32 segments
        and pay one packed download for the whole group."""
        q = np.asarray(queries, np.float32)
        if self.similarity == "cosine":
            qn = np.linalg.norm(q, axis=1, keepdims=True)
            q = q / np.where(qn == 0, 1.0, qn)
        if q.shape[1] != self.d_pad:
            q = np.pad(q, ((0, 0), (0, self.d_pad - q.shape[1])))
        dots = int8_dot_scores(
            jnp.asarray(q), self.qvecs, self.scales, interpret=interpret
        )
        if self.similarity in ("cosine", "dot_product"):
            scores = (1.0 + dots) / 2.0
        elif self.similarity == "max_inner_product":
            scores = jnp.where(dots < 0, 1.0 / (1.0 - dots), dots + 1.0)
        else:
            raise ValueError(
                f"unsupported similarity for int8 [{self.similarity}]"
            )
        valid = jnp.arange(self.n_pad) < self.n
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
        return jax.lax.top_k(scores, min(k, self.n))
