"""Compile the query path's Pallas kernels for a described TPU v5e chip.

The CPU suite runs the kernels in interpret mode (or not at all: off-TPU the
ops dispatch to the jnp oracles), and the interpreter accepts block shapes,
reductions and reshapes that Mosaic refuses. These tests compile each
kernel with ``interpret=False`` for one chip of a described (not attached)
``v5e:2x2`` topology at the SIFT smoke widths (``chip_smoke.py``: n=300k,
d=128, gamma=0.8, max_L=32), so a kernel the chip's compiler would refuse
fails here without a chip.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.probabilities import solve_params
from repro.core.query import QueryConfig
from repro.kernels.bucket_probe.ops import bucket_probe
from repro.kernels.l2_distance.ops import l2_distance_gathered
from repro.kernels.lsh_hash import lsh_hash_all_radii, lsh_hash_all_radii_ref
from repro.kernels.lsh_hash.ops import _SCOPED_VMEM_BYTES, _vmem_bytes

# SIFT-shaped data at n=300k scales to x_max ~4.67 -> a 7-radius schedule
PARAMS = solve_params(300_000, 128, gamma=0.8, max_L=32, x_max=4.67)
CFG = QueryConfig.from_params(PARAMS, k=10)
Q = 256             # queries per dispatch in the smoke
NB = 8_300_000      # block rows of the n=300k index (measured build)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile cannot be read back from the persistent
    # cache, so keep it out of any cache a caller may have enabled
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    jax.clear_caches()


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _hash_args(one_chip):
    r, L, m, d = PARAMS.r, PARAMS.L, PARAMS.m, PARAMS.d
    return (_shape(one_chip, (Q, d)), _shape(one_chip, (r, L, m, d)),
            _shape(one_chip, (r, L, m)),
            _shape(one_chip, (r, L, m), jnp.uint32))


def _hash_fn(force_pallas=True):
    kw = dict(w=CFG.w, radii=CFG.radii, u=CFG.u, fp_bits=CFG.fp_bits)
    if not force_pallas:
        return lambda *a: lsh_hash_all_radii_ref(*a, **kw)
    return lambda *a: lsh_hash_all_radii(*a, interpret=False,
                                         force_pallas=True, **kw)


def test_lsh_hash_compiles_at_sift_width(one_chip):
    assert (PARAMS.r, PARAMS.L, PARAMS.m) == (7, 32, 21)
    # the VMEM gate lets these widths through (no reference fallback)
    assert _vmem_bytes(PARAMS.d, PARAMS.r * PARAMS.L, PARAMS.m, 256) \
        <= _SCOPED_VMEM_BYTES
    assert "tpu_custom_call" in _compiled_text(_hash_fn(),
                                               *_hash_args(one_chip))


def test_bucket_probe_compiles_at_sift_width(one_chip):
    G = Q * CFG.max_chain * CFG.L
    args = (_shape(one_chip, (G,), jnp.int32),
            _shape(one_chip, (G,), jnp.int32),
            _shape(one_chip, (NB, 128), jnp.int32),
            _shape(one_chip, (NB, 128), jnp.int32))
    fn = lambda *a: bucket_probe(*a, interpret=False, use_pallas=True)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_l2_distance_gathered_compiles_at_sift_width(one_chip):
    d, sbuf = PARAMS.d, CFG.sbuf
    args = (_shape(one_chip, (Q, d)), _shape(one_chip, (Q, sbuf, d)),
            _shape(one_chip, (Q, sbuf)), _shape(one_chip, (Q,)))
    fn = lambda *a: l2_distance_gathered(*a, interpret=False,
                                         force_pallas=True)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_query_hashing_lowers_at_f32_precision(one_chip):
    """Kernel and oracle hash queries with f32 products (HIGHEST), not the
    TPU's default one-pass bf16 matmul: the index is built from float64
    projections, so a bf16 query hash lands across its floor() boundaries."""
    args = _hash_args(one_chip)
    kernel_jaxpr = str(jax.make_jaxpr(_hash_fn())(*args))
    assert "dot_general" in kernel_jaxpr
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in kernel_jaxpr
    oracle_hlo = jax.jit(_hash_fn(force_pallas=False)).lower(*args).as_text()
    n_dots = oracle_hlo.count("stablehlo.dot_general")
    assert n_dots == PARAMS.r
    assert oracle_hlo.count("precision = [HIGHEST, HIGHEST]") == n_dots
