"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each kernel is lowered and compiled for one
device of a described ``v5e:2x2`` topology, which the installed TPU
compiler accepts without an attached chip. A compile that Mosaic refuses
(an op with no TPU lowering, a block over the VMEM budget) fails here,
at no chip time. Compiled code cannot be read back from the persistent
cache without a chip, so the cache is off around these compiles.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.ndv.types import ColumnBatch
from repro.kernels import fused_estimate as fused
from repro.kernels import hll, minmax_scan, newton_ndv


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch(sharding, b, r):
    plane = lambda dt=jnp.float32: _shape(sharding, (b, r), dt)  # noqa: E731
    col = lambda dt=jnp.float32: _shape(sharding, (b,), dt)  # noqa: E731
    return ColumnBatch(
        chunk_S=plane(), chunk_rows=plane(), chunk_nulls=plane(),
        chunk_dict_encoded=plane(jnp.bool_), N=col(), nulls=col(),
        n_groups=col(jnp.int32), mins=plane(), maxs=plane(),
        valid=plane(jnp.bool_), m_min=col(), m_max=col(), mean_len=col(),
        len_sample=col(jnp.int32), fixed_width=col(jnp.bool_),
        int_like=col(jnp.bool_), single_byte=col(jnp.bool_),
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", ["paper", "improved"])
@pytest.mark.parametrize("b,r", [(4096, 256), (32768, 256)])
def test_fused_estimate_compiles(one_chip, b, r, mode):
    text = _compiled_text(
        lambda batch, sb: fused.fused_estimate(
            batch, sb, mode=mode, interpret=False
        ),
        _batch(one_chip, b, r),
        _shape(one_chip, (b,)),
    )
    assert "tpu_custom_call" in text


def test_fused_estimate_compiles_at_widest_groups(one_chip):
    # MAX_GROUPS: the 8-column floor block, with the raised scoped VMEM.
    r = fused.MAX_GROUPS
    text = _compiled_text(
        lambda batch: fused.fused_estimate(
            batch, mode="paper", interpret=False
        ),
        _batch(one_chip, 64, r),
    )
    assert "tpu_custom_call" in text


def test_coupon_newton_compiles(one_chip):
    flat = _shape(one_chip, (4096 * 256,))
    text = _compiled_text(
        lambda m, n: newton_ndv.coupon_newton(m, n, interpret=False),
        flat, flat,
    )
    assert "tpu_custom_call" in text


def test_dict_newton_compiles(one_chip):
    flat = _shape(one_chip, (4096 * 256,))
    text = _compiled_text(
        lambda s, rows, nulls, ln: newton_ndv.dict_newton(
            s, rows, nulls, ln, interpret=False
        ),
        flat, flat, flat, flat,
    )
    assert "tpu_custom_call" in text


def test_minmax_scan_compiles(one_chip):
    plane = _shape(one_chip, (4096, 256))
    text = _compiled_text(
        lambda lo, hi, valid: minmax_scan.minmax_scan(
            lo, hi, valid, interpret=False
        ),
        plane, plane, _shape(one_chip, (4096, 256), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_hll_fold_compiles(one_chip):
    text = _compiled_text(
        lambda keys, valid: hll.hll_fold(keys, valid, interpret=False),
        _shape(one_chip, (4096, 256), jnp.uint32),
        _shape(one_chip, (4096, 256), jnp.bool_),
    )
    assert "tpu_custom_call" in text
