"""Jitted wrapper: pads queries and dispatches kernel vs oracle.

On CPU (tests / benches) the oracle path runs; on TPU the Pallas kernel.
``interpret=True`` forces the kernel body through the Pallas interpreter for
correctness validation anywhere.
"""
import jax
import jax.numpy as jnp

from repro.kernels.bucket_probe.kernel import SUBLANES, bucket_probe
from repro.kernels.bucket_probe.ref import bucket_probe_ref

# VMEM a grid step may hold: two [q_block, 8, W] slab buffers plus the
# double-buffered [q_block, W] output tile, well inside the 16 MiB scoped
# default of a v5e core
VMEM_BUDGET = 8 << 20


def tile_queries(q_block: int, width: int) -> int:
    """Queries per grid step: ``q_block`` rounded up to the 8-row tiling and
    capped so that one step's buffers fit ``VMEM_BUDGET`` at this width."""
    per_query = width * 4 * (2 * SUBLANES + 2)
    cap = max(SUBLANES, VMEM_BUDGET // per_query // SUBLANES * SUBLANES)
    return min(-(-q_block // SUBLANES) * SUBLANES, cap)


def probe(bucket_hashes, bucket_payload, queries, bucket_bits, *,
          use_kernel=None, interpret=None, q_block=256):
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = on_tpu if use_kernel is None else use_kernel
    if not use_kernel:
        return bucket_probe_ref(bucket_hashes, bucket_payload, queries,
                                bucket_bits)
    q_block = tile_queries(q_block, bucket_hashes.shape[1])
    pad = (-queries.shape[0]) % q_block
    q = jnp.pad(queries, (0, pad), constant_values=jnp.uint32(0xFFFFFFFF))
    out = bucket_probe(bucket_hashes, bucket_payload, q,
                       bucket_bits=bucket_bits, q_block=q_block,
                       interpret=bool(interpret) and not on_tpu)
    return out[: queries.shape[0]]
