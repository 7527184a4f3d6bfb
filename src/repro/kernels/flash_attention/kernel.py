"""Pallas TPU kernel: blockwise online-softmax (flash) attention forward.

Grid (batch*kv_head*group, q_blocks); each step keeps a [Tq, D] query tile +
running (m, l, acc) in VMEM and streams KV tiles — the score matrix never
touches HBM, which removes the memory-term bottleneck the dry-run measures
for the pure-JAX chunked path (EXPERIMENTS §Perf).  MXU-aligned tiles
(Tq, Tk multiples of 128; D = head_dim 64/128).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block, causal, sq, skv):
    # q_ref: [Tq, D]; k_ref/v_ref: [Skv, D] (whole kv stream for this head)
    qi = pl.program_id(1)
    tq = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[...].astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    nk = skv // kv_block

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * kv_block, kv_block), :]
        v = v_ref[pl.ds(j * kv_block, kv_block), :]
        s = q @ k.astype(jnp.float32).T * scale            # [Tq, Tk]
        if causal:
            qpos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, kv_block), 0)
            kpos = j * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (tq, kv_block), 1)
            s = jnp.where(qpos + (skv - sq) >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((tq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((tq,), jnp.float32)
    a0 = jnp.zeros((tq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "kv_block", "causal",
                                             "interpret"))
def flash_attention(q, k, v, *, q_block=128, kv_block=128, causal=True,
                    interpret=False):
    """q: [BH, Sq, D]; k/v: [BH, Skv, D] (kv already expanded per q-head
    group).  Returns [BH, Sq, D]."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % q_block == 0 and skv % kv_block == 0
    grid = (bh, sq // q_block)
    return pl.pallas_call(
        functools.partial(_flash_kernel, kv_block=kv_block, causal=causal,
                          sq=sq, skv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, q_block, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, skv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, skv, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
