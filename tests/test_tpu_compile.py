"""The data-path Pallas kernels compile for a TPU v5e chip (Mosaic, not the
interpreter) at the executor's real sizes: morsels of ``SUM_ROW_CAP`` rows,
tile 256, up to ``_SEG_GROUP_CAP`` groups.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  The topology is described inside a fixture (never at import),
so only the worker that runs this file loads the TPU library; where it
cannot be described, every test here skips.  The persistent compilation
cache is off around the compiles: an entry written for a described chip
cannot be read back without one."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backend import _SEG_GROUP_CAP
from repro.kernels import filter_select, fused_pipeline, project_arith, segment_reduce
from repro.kernels.segment_reduce import SUM_ROW_CAP

N = SUM_ROW_CAP
TILE = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


I32, F32 = jnp.int32, jnp.float32
F32_DESCRS = (
    ("add", ("mul", ("col", 0), ("lit", 2.0)), ("lit", 1.0)),
    ("mul", ("sub", ("col", 0), ("col", 1)), ("lit", 0.75)),
)


@pytest.mark.parametrize("segmented", [False, True], ids=["stream", "segmented"])
def test_fused_chain_tiles_compiles(one_chip, segmented):
    ng = _SEG_GROUP_CAP if segmented else 8
    fn = functools.partial(
        fused_pipeline.fused_chain_tiles,
        op="gt",
        kind="f32",
        descrs_f=F32_DESCRS,
        descrs_i=(("mul", ("col", 0), ("lit", 3)),),
        csums=(0,) if segmented else (),
        fns_f=("min", "max"),
        fns_i=("max",),
        with_gidx=segmented,
        segmented=segmented,
        ngroups=ng,
        tile=TILE,
        interpret=False,
    )
    _compile(
        fn,
        one_chip,
        ((4,), I32),  # scalars
        ((N, 1), I32),  # pred planes
        ((N,), I32),  # gidx
        ((N, 5), I32),  # passthrough planes
        ((N, 16), I32),  # limb planes
        ((N, 2), F32),  # f32 min/max
        ((N, 1), I32),  # int min/max
        ((N, 2), F32),  # f32 arithmetic inputs
        ((N, 1), I32),  # i32 arithmetic inputs
    )


@pytest.mark.parametrize("kind,planes", [("f32", 1), ("i32", 1), ("i64", 2)])
def test_filter_select_planes_compiles(one_chip, kind, planes):
    fn = functools.partial(filter_select.filter_select_planes, op="le", kind=kind, tile=TILE, interpret=False)
    _compile(fn, one_chip, ((N, planes), I32), ((N, 5), I32), ((3,), I32))


def test_segment_sum_tiles_compiles(one_chip):
    def fn(gidx, limbs, n_rows):
        return segment_reduce.segment_sum_tiles(gidx, limbs, n_rows, _SEG_GROUP_CAP, tile=TILE, interpret=False)

    _compile(fn, one_chip, ((N,), I32), ((N, 16), I32), ((), I32))


@pytest.mark.parametrize("dtype", [F32, I32], ids=["f32", "i32"])
def test_segment_minmax_tiles_compiles(one_chip, dtype):
    def fn(gidx, vals, n_rows):
        return segment_reduce.segment_minmax_tiles(
            gidx, vals, n_rows, _SEG_GROUP_CAP, ("min", "max"), tile=TILE, interpret=False
        )

    _compile(fn, one_chip, ((N,), I32), ((N, 2), dtype), ((), I32))


@pytest.mark.parametrize(
    "dtype,descrs",
    [
        (F32, F32_DESCRS),
        (I32, (("sub", ("mul", ("col", 0), ("lit", 3)), ("col", 1)),)),
    ],
    ids=["f32", "i32"],
)
def test_project_tiles_compiles(one_chip, dtype, descrs):
    fn = functools.partial(project_arith.project_tiles, descrs=descrs, tile=TILE, interpret=False)
    _compile(fn, one_chip, ((N, 2), dtype))
