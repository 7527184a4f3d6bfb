"""Pallas TPU kernel: QCR correlation scores over padded sketch groups.

Input layout: one row per (table, join_col, num_col) group holding up to H
h-sampled (quadrant, query-bit) pairs.  The kernel fuses the agreement
compare, masked reduction and the (2a-n)/n epilogue in VMEM — one HBM pass
over the sketch matrix (the correlation seeker's scoring hot loop).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _qcr_kernel(quad_ref, qbit_ref, valid_ref, out_ref):
    # v5e has no int8 vector compare (and Mosaic folds a widening to int32
    # back into one): compare the small integers exactly in f32
    quad = quad_ref[...].astype(jnp.float32)
    qbit = qbit_ref[...].astype(jnp.float32)
    valid = valid_ref[...]
    v = valid.astype(jnp.float32)
    agree = jnp.where(valid & (quad == qbit), 1.0, 0.0)
    n = jnp.sum(v, axis=1, keepdims=True)
    a = jnp.sum(agree, axis=1, keepdims=True)
    qcr = jnp.abs(2.0 * a - n) / jnp.maximum(n, 1.0)
    out_ref[...] = jnp.where(n >= 3, qcr, 0.0)


def _qcr_seg_kernel(agree_ref, all_ref, out_ref, *, min_support):
    n = all_ref[...]
    a = agree_ref[...]
    qcr = jnp.abs(2.0 * a - n) / jnp.maximum(n, 1.0)
    out_ref[...] = jnp.where(n >= min_support, qcr, 0.0)


@functools.partial(jax.jit, static_argnames=("min_support", "d_block",
                                             "interpret"))
def qcr_segments(n_agree, n_all, *, min_support=3, d_block=2048,
                 interpret=False):
    """Fused QCR epilogue over segment sums: n_agree/n_all f32 [D] (one entry
    per (table, join_col, num_col) triple) -> |2a - n| / n with the support
    floor.  The correlation seeker's scoring stage."""
    d = n_agree.shape[0]
    assert d % d_block == 0
    grid = (d // d_block,)
    return pl.pallas_call(
        functools.partial(_qcr_seg_kernel, min_support=min_support),
        grid=grid,
        in_specs=[pl.BlockSpec((d_block,), lambda i: (i,))] * 2,
        out_specs=pl.BlockSpec((d_block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(n_agree, n_all)


@functools.partial(jax.jit, static_argnames=("g_block", "interpret"))
def qcr_score(quadrants, qbits, valid, *, g_block=128, interpret=False):
    g, h = quadrants.shape
    assert g % g_block == 0
    grid = (g // g_block,)
    return pl.pallas_call(
        _qcr_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((g_block, h), lambda i: (i, 0))] * 3,
        # a [G, 1] column: a rank-1 [g_block] block would not match the
        # 1024-element tiling XLA gives a 1-D f32 array on the TPU
        out_specs=pl.BlockSpec((g_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 1), jnp.float32),
        interpret=interpret,
    )(quadrants, qbits, valid)[:, 0]
