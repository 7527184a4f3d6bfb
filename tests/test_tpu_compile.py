"""The discovery kernels and a fused seeker program compile for a TPU v5e.

Nothing runs: each program is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which refuses what the chip's compiler would
refuse — unaligned blocks, too much VMEM, a program that does not fit 16 GB
of HBM — at no chip time.  The topology is described inside a fixture, so a
worker that is not given this file never loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import seekers as seek
from repro.core.fused import MAX_CHUNK, SETS_PER_SEEKER
from repro.core.match import EngineConfig, MatchEngine
from repro.dist.shard import GITTABLES_SCALE
from repro.kernels.bucket_probe import ops as bucket_ops
from repro.kernels.bucket_probe.kernel import bucket_probe
from repro.kernels.qcr_score.kernel import qcr_segments
from repro.kernels.superkey_filter.kernel import superkey_filter_rows

HBM_BYTES = 16 * 2 ** 30          # one v5e chip

# the served path at the chip smoke's scale (chip_smoke.py): a 4096-bucket
# table ~1.5k wide, two segments of up to 1024 matches, 32768 table slots
BUCKET_ROWS, BUCKET_WIDTH = 4096, 1536
WINDOW = 2 * 1024
TABLE_SLOTS, MAX_COLS = 32768, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory on one described chip, with the persistent
    compilation cache off (its entries cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def kernel_programs(sds):
    q_block = bucket_ops.tile_queries(256, BUCKET_WIDTH)
    return {
        "bucket_probe": (
            functools.partial(bucket_probe, bucket_bits=12, q_block=q_block),
            (sds((BUCKET_ROWS, BUCKET_WIDTH), jnp.uint32),
             sds((BUCKET_ROWS, BUCKET_WIDTH), jnp.int32),
             sds((16 * q_block,), jnp.uint32))),
        "superkey_filter_rows": (
            functools.partial(superkey_filter_rows, t_block=8),
            (sds((256, WINDOW), jnp.uint32), sds((256, WINDOW), jnp.uint32),
             sds((256,), jnp.uint32), sds((256,), jnp.uint32))),
        "qcr_segments": (
            functools.partial(qcr_segments, d_block=2048),
            (sds((TABLE_SLOTS * MAX_COLS * MAX_COLS,), jnp.float32),) * 2),
    }


@pytest.mark.parametrize("name", ["bucket_probe", "superkey_filter_rows",
                                  "qcr_segments"])
def test_kernel_lowers_for_v5e(sds, name):
    fn, args = kernel_programs(sds)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def engine_shapes(sds, n_postings: int, n_numeric: int, cfg):
    """A MatchEngine whose arrays are shapes (the layout of
    ``UnifiedIndex.device_arrays``)."""
    u32, i32, i8 = jnp.uint32, jnp.int32, jnp.int8
    dev = {k: sds((n_postings,), dt) for k, dt in (
        ("hash", u32), ("table", i32), ("col", i32), ("row", i32),
        ("sk_lo", u32), ("sk_hi", u32), ("quadrant", i8),
        ("rank_conv", i32), ("rank_rand", i32))}
    dev["row_cells"] = sds((n_postings, cfg.max_cols), u32)
    dev.update({k: sds((n_numeric,), dt) for k, dt in (
        ("num_rowkey", i32), ("num_table", i32), ("num_col", i32),
        ("num_quadrant", i8), ("num_rank_conv", i32),
        ("num_rank_rand", i32))})
    if cfg.backend == "sorted":
        return MatchEngine(dev, None, None, cfg)
    tables = tuple(sds((BUCKET_ROWS, w), dt) for w in cfg.bucket_widths
                   for dt in (u32, i32))
    return MatchEngine(dev, tables[0::2], tables[1::2], cfg,
                       alive=sds((cfg.n_tables,), jnp.bool_))


def test_sc_seeker_fits_one_chip_at_gittables_quarter(sds):
    """The fused SC program over a quarter of GitTables (one shard of a
    four-chip host) compiles and fits one chip's HBM."""
    n = GITTABLES_SCALE["n_postings"] // 4
    nnum = GITTABLES_SCALE["n_numeric"] // 4
    n_tables = GITTABLES_SCALE["n_tables"] // 4
    cfg = EngineConfig(backend="sorted", interpret=False, bucket_bits=12,
                       bucket_widths=(), seg_bounds=((0, n, n),),
                       num_bounds=((0, nnum, nnum),), n_tables=n_tables,
                       max_cols=GITTABLES_SCALE["max_cols"],
                       row_stride=GITTABLES_SCALE["row_stride"])
    eng = engine_shapes(sds, n, nnum, cfg)
    nq = 1024
    compiled = seek.sc_seeker_seg.lower(
        eng, sds((nq,), jnp.uint32), sds((nq,), jnp.bool_),
        sds((nq,), jnp.int32), sds((SETS_PER_SEEKER * 4, 4), jnp.bool_),
        sds((), jnp.int32),
        chunk=MAX_CHUNK, n_tables=n_tables,
        max_cols=GITTABLES_SCALE["max_cols"]).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_sc_seeker_fits_one_chip_at_webtables_zipf(sds):
    """The fused SC program over the Zipf web-table lake with a batch's
    2,048 distinct values and 16 seekers compiles and fits one chip's HBM:
    its holder-set counts are [64 x 262,144] float32 (32,768 table slots
    of 8 columns), and the product that turns them into seeker counts is
    a dense dot."""
    n, nnum = 1 << 21, 1 << 19
    cfg = EngineConfig(backend="sorted", interpret=False, bucket_bits=12,
                       bucket_widths=(), seg_bounds=((0, n, 1_880_885),),
                       num_bounds=((0, nnum, 340_757),),
                       n_tables=TABLE_SLOTS, max_cols=8, row_stride=256)
    eng = engine_shapes(sds, n, nnum, cfg)
    nq, nsp = 2048, 16
    compiled = seek.sc_seeker_seg.lower(
        eng, sds((nq,), jnp.uint32), sds((nq,), jnp.bool_),
        sds((nq,), jnp.int32), sds((SETS_PER_SEEKER * nsp, nsp), jnp.bool_),
        sds((), jnp.int32), chunk=MAX_CHUNK, n_tables=TABLE_SLOTS,
        max_cols=8).compile()
    assert "dot" in compiled.as_text() or "convolution" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_mc_seeker_fits_one_chip_at_webtables_zipf(sds):
    """The fused MC program over the Zipf web-table lake (1,880,885
    postings in a 2^21 run, 32,768 table slots) with a batch's 1,024 tuples
    compiles and fits one chip's HBM: the validation's memory follows the
    chunk, whatever the postings of the values."""
    n, nnum = 1 << 21, 1 << 19
    cfg = EngineConfig(backend="sorted", interpret=False, bucket_bits=12,
                       bucket_widths=(), seg_bounds=((0, n, 1_880_885),),
                       num_bounds=((0, nnum, 340_757),),
                       n_tables=TABLE_SLOTS, max_cols=8, row_stride=256)
    eng = engine_shapes(sds, n, nnum, cfg)
    nt = 1024
    u32, i32 = jnp.uint32, jnp.int32
    compiled = seek.mc_seeker_seg.lower(
        eng, sds((nt, 2), u32), sds((nt,), i32), sds((nt,), i32),
        sds((), i32), chunk=MAX_CHUNK, n_seekers=16, n_tables=TABLE_SLOTS,
        n_cols=2).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def bucket_seeker_programs(sds):
    """The fused SC, MC and C programs of the ``bucket`` backend over a live
    store of the chip smoke's shape: a ~5.4M-posting base segment and one
    small delta, each with its own bucket table."""
    base, delta = 1 << 23, 64
    nbase, ndelta = 1 << 21, 64
    cfg = EngineConfig(backend="bucket", interpret=False, bucket_bits=12,
                       bucket_widths=(BUCKET_WIDTH, 128),
                       seg_bounds=((0, base, 5_400_000), (base, delta, 40)),
                       num_bounds=((0, nbase, 1_350_000),
                                   (nbase, ndelta, 40)),
                       n_tables=TABLE_SLOTS, max_cols=MAX_COLS, row_stride=64)
    eng = engine_shapes(sds, base + delta, nbase + ndelta, cfg)
    nq, nt = 64, 16
    u32, i32, i8, b = jnp.uint32, jnp.int32, jnp.int8, jnp.bool_
    common = dict(chunk=MAX_CHUNK, n_seekers=2, n_tables=TABLE_SLOTS)
    return {
        "sc": (seek.sc_seeker_seg,
               (eng, sds((nq,), u32), sds((nq,), b), sds((nq,), i32),
                sds((SETS_PER_SEEKER * 2, 2), b), sds((), i32)),
               dict(chunk=MAX_CHUNK, n_tables=TABLE_SLOTS,
                    max_cols=MAX_COLS)),
        "mc": (seek.mc_seeker_seg,
               (eng, sds((nt, 2), u32), sds((nt,), i32), sds((nt,), i32),
                sds((), i32)),
               dict(common, n_cols=2)),
        "c": (seek.c_seeker_seg,
              (eng, sds((nq,), u32), sds((nq,), b), sds((nq,), i8),
               sds((nq,), i32), sds((), i32)),
              dict(common, row_cap=8, max_cols=MAX_COLS, h_sample=256,
                   row_stride=64)),
    }


@pytest.mark.parametrize("kind", ["sc", "mc", "c"])
def test_bucket_seekers_lower_for_v5e(sds, kind):
    """Each kernel also lowers inside the fused program that calls it."""
    fn, args, kw = bucket_seeker_programs(sds)[kind]
    assert "tpu_custom_call" in fn.lower(*args, **kw).compile().as_text()
