"""Pallas TPU kernel: padded radix-bucket hash probe.

The unified index's bucket table ([2^bits, W] hashes + payloads) stays in
HBM (``pl.ANY``).  Bucket rows and queries are scalar-prefetched into SMEM.
Each grid step owns a tile of ``q_block`` queries: it DMAs, per query, the
sublane-aligned 8-row slab of the table that holds the query's bucket row
into VMEM (a bounded, rectangular gather — the TPU replacement for B-tree
pointer chasing; HBM slices must be aligned to the (8, 128) tiling, so a
single row cannot be fetched alone), then compares the slab against the
query, keeps only the bucket's own row, and emits the matching payload
offsets (``-1`` elsewhere) as one output row.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8          # rows of one (8, 128) tile: the unit of an HBM slice


def _probe_kernel(rows_ref, q_ref, bh_hbm, bp_hbm, out_ref, bh_buf, bp_buf,
                  sem, *, q_block):
    base = pl.program_id(0) * q_block

    def copies(j):
        start = pl.multiple_of(rows_ref[base + j] // SUBLANES * SUBLANES,
                               SUBLANES)
        return (pltpu.make_async_copy(bh_hbm.at[pl.ds(start, SUBLANES)],
                                      bh_buf.at[j], sem.at[0]),
                pltpu.make_async_copy(bp_hbm.at[pl.ds(start, SUBLANES)],
                                      bp_buf.at[j], sem.at[1]))

    def start(j, carry):
        for cp in copies(j):
            cp.start()
        return carry

    def wait(j, carry):
        for cp in copies(j):
            cp.wait()
        return carry

    def match(j, carry):
        hashes = bh_buf[j]                                  # [8, W]
        slab_row = jax.lax.broadcasted_iota(jnp.int32, hashes.shape, 0)
        hit = (hashes == q_ref[base + j]) & \
            (slab_row == rows_ref[base + j] % SUBLANES)
        out_ref[pl.ds(j, 1), :] = jnp.max(
            jnp.where(hit, bp_buf[j], -1), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, q_block, start, 0)
    jax.lax.fori_loop(0, q_block, wait, 0)
    jax.lax.fori_loop(0, q_block, match, 0)


@functools.partial(jax.jit, static_argnames=("bucket_bits", "q_block",
                                             "interpret"))
def bucket_probe(bucket_hashes, bucket_payload, queries, *, bucket_bits,
                 q_block=256, interpret=False):
    """queries [M] u32 against the [2^bits, W] table -> [M, W] i32 payload
    where the hash matches, else -1.  ``q_block`` queries per grid step: a
    multiple of 8 (or all of M), with M a multiple of it."""
    m = queries.shape[0]
    n_buckets, width = bucket_hashes.shape
    assert m % q_block == 0, "pad queries to q_block"
    assert n_buckets % SUBLANES == 0, "bucket_bits must be at least 3"
    rows = (queries >> (32 - bucket_bits)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // q_block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # table stays in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((q_block, width), lambda i, rows, q: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_block, SUBLANES, width), bucket_hashes.dtype),
            pltpu.VMEM((q_block, SUBLANES, width), bucket_payload.dtype),
            pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_probe_kernel, q_block=q_block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, width), jnp.int32),
        interpret=interpret,
    )(rows, queries, bucket_hashes, bucket_payload)
