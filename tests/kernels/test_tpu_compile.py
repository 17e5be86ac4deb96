"""Ahead-of-time compiles of the main-path Pallas kernels, and of Balance's
2:1 eval program, for a TPU v5e chip.

Interpret mode (every other kernel test) cannot show what the TPU compiler
refuses — a nested boolean select in the d=3 inside-root test once passed
every interpret-mode test and failed in Mosaic.  These tests compile the
kernels at a deployment batch size (N = 2^20 elements) for one chip of a
described `v5e:2x2` topology; no chip is needed, and nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and collection must not depend on
it.  Keep every such compile in this one file.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import ECLASS_HEX, ECLASS_SIMPLEX
from repro.kernels import sfc

N = 1 << 20
BLOCK = sfc.DEFAULT_BLOCK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _i32(sh, shape=(N,)):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)


def _u32(sh, shape=(N,)):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sh)


@pytest.mark.parametrize("eclass", [ECLASS_SIMPLEX, ECLASS_HEX],
                         ids=["simplex", "hex"])
def test_face_sweep_compiles(one_chip, eclass):
    d = 3
    text = _compile_text(
        lambda *a: sfc.face_sweep_kernel(d, *a, block=BLOCK, interpret=False,
                                         eclass=eclass),
        *[_i32(one_chip) for _ in range(d + 2)])
    assert "tpu_custom_call" in text


def test_inside_root_compiles(one_chip):
    d = 3
    text = _compile_text(
        lambda *a: sfc.inside_root_kernel(d, *a, block=BLOCK, interpret=False),
        *[_i32(one_chip) for _ in range(d + 2)])
    assert "tpu_custom_call" in text


def test_decode_compiles(one_chip):
    d = 3
    text = _compile_text(
        lambda *a: sfc.decode_kernel(d, *a, block=BLOCK, interpret=False),
        _u32(one_chip), _u32(one_chip), _i32(one_chip))
    assert "tpu_custom_call" in text


def test_children_compiles(one_chip):
    d = 3
    text = _compile_text(
        lambda *a: sfc.children_kernel(d, *a, block=BLOCK, interpret=False),
        *[_i32(one_chip) for _ in range(d + 2)])
    assert "tpu_custom_call" in text


def test_eval_route_compiles(one_chip):
    d = 3
    pairs = (sfc.faces_per_element(d) * N,)  # flat (face, element) pairs
    text = _compile_text(
        lambda *a: sfc.eval_route_kernel(d, *a, block=BLOCK, interpret=False),
        _i32(one_chip, pairs), _u32(one_chip, pairs), _u32(one_chip, pairs),
        _i32(one_chip, pairs), _i32(one_chip, (8,)), _u32(one_chip, (8,)),
        _u32(one_chip, (8,)))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def eval_need_texts(one_chip):
    """Balance's 2:1 eval (`eval_need`) compiled for both element classes
    over N elements and an N-row leaf table.  Its merged sort takes the TPU
    compiler minutes on a CPU host, so the two compiles run side by side."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import batch
    from repro.core.ops import get_ops

    def compile_one(eclass):
        pairs = (get_ops(3, eclass).nf, N)
        flags = jax.ShapeDtypeStruct(pairs, jnp.bool_, sharding=one_chip)
        marker = (_i32(one_chip, ()), _u32(one_chip, ()), _u32(one_chip, ()),
                  jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))
        return batch._eval_progs(3, eclass)["need"].lower(
            _i32(one_chip, pairs), _u32(one_chip, pairs), _u32(one_chip, pairs),
            flags, _i32(one_chip),
            _i32(one_chip), _u32(one_chip), _u32(one_chip), _i32(one_chip),
            *marker, *marker).compile().as_text()

    eclasses = (ECLASS_SIMPLEX, ECLASS_HEX)
    with ThreadPoolExecutor(len(eclasses)) as pool:
        return dict(zip(eclasses, pool.map(compile_one, eclasses)))


@pytest.mark.parametrize("eclass", [ECLASS_SIMPLEX, ECLASS_HEX],
                         ids=["simplex", "hex"])
def test_eval_need_compiles(eval_need_texts, eclass):
    """The eval's lower bounds are one merged sort, not a binary search,
    which gathers three table words log2(N) times per (face, element)
    pair: a sort, and no gathers beyond the level-count lookups."""
    text = eval_need_texts[eclass]
    assert " sort(" in text
    assert text.count(" gather(") <= 4


def test_tree_transform_compiles(one_chip):
    d = 3
    # a reflecting signed permutation: every axis moves and one flips sign
    M = ((0, 0, -1), (1, 0, 0), (0, 1, 0))
    c = (1 << 21, 0, 0)
    tmap = tuple(reversed(range(math.factorial(d))))
    text = _compile_text(
        lambda *a: sfc.tree_transform_kernel(d, M, c, tmap, *a, block=BLOCK,
                                             interpret=False),
        *[_i32(one_chip) for _ in range(d + 2)])
    assert "tpu_custom_call" in text


def test_eval_route_small_bucket_compiles(one_chip, monkeypatch):
    """The smallest padding bucket (16 elements) through the jitted wrapper:
    a 1-D block must be a multiple of 128 lanes or the whole array, which
    the flattened (face, element) pairs must respect."""
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    d, m = 3, 16
    tile = (sfc.faces_per_element(d), m)
    text = _compile_text(
        lambda *a: kops.eval_route(d, *a, m),
        _i32(one_chip, tile), _u32(one_chip, tile), _u32(one_chip, tile),
        _i32(one_chip, tile), _i32(one_chip, (8,)), _u32(one_chip, (8,)),
        _u32(one_chip, (8,)))
    assert "tpu_custom_call" in text
