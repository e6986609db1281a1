"""Pallas TPU kernel: dot products of a query with the stored rows of
NAMED 128-row blocks, for the filtered kNN family's lead route
(`scoring.knn_topk_lead`).

Why a kernel: the TPU holds a `byte` field's rows `s8[N, 192]` with the
ROWS along the lanes (the layout without padding, which the scan
`queries @ vectors.T` streams at the HBM's pace), so one stored row is
192 bytes strewn over six 4 KB tiles and XLA has no gather for it:
`vectors[docs]` compiles to a relayout of the whole matrix (2.56 GB of
temporaries and ~10 ms a launch at 10M rows, more than the scan), a
gather of 128-row slabs to a sequential loop of one dynamic-slice a
candidate (14.5 ms for 8,192). What the hardware can do is fetch a
candidate's BLOCK, the 128 rows x d columns around it (24 KB: whole
tiles), by one strided DMA, many in flight: this kernel. The transposed
view it reads is a bitcast of the resident rows (no temporaries).

Measured on the TPU v5e at 10M x 192 int8 rows (PERF.md section 6,
PR 50; device ms a launch, launches pipelined): 8,192 candidates 0.593,
32,768 2.297: ~72 ns a candidate, of which the DMAs' issue 47 and the
products 28; 1,024 of 8,192 slots used 0.21 (a skipped slot costs
nothing). Two buffers across grid steps read 0.554 / 2.154 (the core
issues DMAs and products from one instruction stream: little overlaps)
and are not kept.

Compiled by Mosaic unless the caller passes `interpret=True` (the CPU
tests do, explicitly), as ops/fuzzy.py's blocked expansion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # rows of a block: the lanes of one tile
GROUP = 64  # candidates a grid step fetches and scores
SUB = 8  # candidates a pass of the products converts to float32 at once


def _block_dots_kernel(count_ref, blk_ref, q_ref, vt_ref, out_ref, buf, sem):
    b, j = pl.program_id(0), pl.program_id(1)
    base = (b * pl.num_programs(1) + j) * GROUP

    def window(g):
        first = pl.multiple_of(blk_ref[base + g] * BLOCK, BLOCK)
        return pltpu.make_async_copy(
            vt_ref.at[:, pl.ds(first, BLOCK)], buf.at[g], sem.at[g])

    def each_named(do):
        def one(g, carry):
            @pl.when(blk_ref[base + g] >= 0)
            def _():
                do(window(g))
            return carry
        jax.lax.fori_loop(0, GROUP, one, 0)

    @pl.when(j * GROUP < count_ref[b])
    def _():
        each_named(lambda dma: dma.start())
        each_named(lambda dma: dma.wait())
        q = q_ref[...][None, :, :]  # [1, d, 1]: along the sublanes

        def products(i, carry):
            rows = buf[pl.ds(i * SUB, SUB)].astype(jnp.float32)
            out_ref[pl.ds(i * SUB, SUB), :] = jnp.sum(rows * q, axis=1)
            return carry

        jax.lax.fori_loop(0, GROUP // SUB, products, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_dots(
    queries: jax.Array,  # float32[B, d]
    vectors: jax.Array,  # [N, d] stored rows, N >= BLOCK
    blk: jax.Array,  # int32[B, M] a block of `vectors` a slot; < 0: none
    count: jax.Array,  # int32[B] the slots of each row that may name one
    interpret: bool = False,
) -> jax.Array:
    """float32[B, M, BLOCK]: out[b, m, l] = queries[b] . vectors[blk[b, m]
    * BLOCK + l], where slot m < count[b] names a block (whole: blk <
    N // BLOCK); anything elsewhere. M a multiple of GROUP. The products
    are float32 multiply-adds on the VPU: exact for whole numbers of 8
    bits in whatever order."""
    B, d = queries.shape
    M = blk.shape[1]
    return pl.pallas_call(
        _block_dots_kernel,
        out_shape=jax.ShapeDtypeStruct((B, M, BLOCK), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, M // GROUP),
            in_specs=[
                pl.BlockSpec((None, d, 1), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (None, GROUP, BLOCK), lambda b, j, *_: (b, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((GROUP, d, BLOCK), vectors.dtype),
                pltpu.SemaphoreType.DMA((GROUP,)),
            ],
        ),
        interpret=interpret,
    )(count, blk.ravel(), queries[:, :, None], vectors.T)
