"""Pallas TPU kernel: XASH superkey containment over 2xu32 lanes.

Tiled elementwise bitwise AND + compare: each grid step streams a [T_blk,
N_blk] tile through VMEM (the MC seeker's bloom pruning stage, MATE-style).
Query digests enter as ``[T, 1]`` columns: a rank-1 block must be a multiple
of 128 on the TPU, while a 2-D ``[T_blk, 1]`` block only needs T_blk to be a
multiple of 8 (its lane dimension spans the whole array).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sk_kernel(sk_lo_ref, sk_hi_ref, q_lo_ref, q_hi_ref, out_ref):
    sk_lo = sk_lo_ref[...]                    # [N_blk]
    sk_hi = sk_hi_ref[...]
    q_lo = q_lo_ref[...]                      # [T_blk, 1]
    q_hi = q_hi_ref[...]
    lo_ok = (sk_lo[None, :] & q_lo) == q_lo
    hi_ok = (sk_hi[None, :] & q_hi) == q_hi
    out_ref[...] = lo_ok & hi_ok


def _sk_rows_kernel(sk_lo_ref, sk_hi_ref, q_lo_ref, q_hi_ref, out_ref):
    sk_lo = sk_lo_ref[...]                    # [T_blk, M]
    sk_hi = sk_hi_ref[...]
    q_lo = q_lo_ref[...]                      # [T_blk, 1]
    q_hi = q_hi_ref[...]
    lo_ok = (sk_lo & q_lo) == q_lo
    hi_ok = (sk_hi & q_hi) == q_hi
    out_ref[...] = lo_ok & hi_ok


@functools.partial(jax.jit, static_argnames=("t_block", "interpret"))
def superkey_filter_rows(sk_lo, sk_hi, q_lo, q_hi, *, t_block=8,
                         interpret=False):
    """Rowwise containment: candidate digests sk_lo/hi [T, M] (the gathered
    probe window of tuple t) against that tuple's own query digest q_lo/hi
    [T] — the MC seeker's bloom pruning stage."""
    t, m = sk_lo.shape
    assert t % t_block == 0
    grid = (t // t_block,)
    return pl.pallas_call(
        _sk_rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_block, m), lambda i: (i, 0)),
            pl.BlockSpec((t_block, m), lambda i: (i, 0)),
            pl.BlockSpec((t_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((t_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t_block, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, m), jnp.bool_),
        interpret=interpret,
    )(sk_lo, sk_hi, q_lo[:, None], q_hi[:, None])


@functools.partial(jax.jit, static_argnames=("t_block", "n_block", "interpret"))
def superkey_filter(sk_lo, sk_hi, q_lo, q_hi, *, t_block=8, n_block=1024,
                    interpret=False):
    n = sk_lo.shape[0]
    t = q_lo.shape[0]
    assert n % n_block == 0 and t % t_block == 0
    grid = (t // t_block, n // n_block)
    return pl.pallas_call(
        _sk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_block,), lambda i, j: (j,)),
            pl.BlockSpec((n_block,), lambda i, j: (j,)),
            pl.BlockSpec((t_block, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((t_block, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t_block, n_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.bool_),
        interpret=interpret,
    )(sk_lo, sk_hi, q_lo[:, None], q_hi[:, None])
