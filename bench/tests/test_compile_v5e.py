"""Every (B, R) pack and planner shape the cells dispatch compiles for a v5e.

The shapes come from the configurations at full scale: each table's
column count and row-group count, bucketed as the program's packer
buckets them (committing a window's snapshots stays inside the bucket),
and each query template's (tables, padded plans). Each is compiled for
one device of a described ``v5e:2x2``, where no chip is attached; the
persistent cache is off around the compiles (a compile for a described
device cannot be read back without one).
"""
import math
import os

import pytest

import checks
import lake


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _buckets(config_name):
    from repro.catalog.packer import BatchPacker

    config = lake.load_json("configs", config_name + ".json")
    per_group = config["rows_per_group"]
    packer = BatchPacker()
    out = set()
    for t in lake.tables_of(config):
        groups = -(-t.rows // per_group)
        out.add(packer.shape_for(len(t.columns), groups))
    return sorted(out)


def _plan_shapes(config_name):
    config = lake.load_json("configs", config_name + ".json")
    queries = lake.load_json("queries", config["queries"] + ".json")
    out = set()
    for t in queries["templates"]:
        n = len(t["tables"])
        # Every order up to the plan budget the traffic leaves at the
        # program's default; a fixed sample of that many past it.
        plans = min(math.factorial(n), checks.DEFAULT_MAX_PLANS)
        out.add((n, 1 << (plans - 1).bit_length()))
    return sorted(out)


@pytest.mark.parametrize("config", ["tpcds_sf1000", "tpch_sf1000"])
def test_packs_compile_with_the_kernel(one_chip, config):
    import jax
    import jax.numpy as jnp

    from repro.core.ndv.types import ColumnBatch
    from repro.kernels.fused_estimate import fused_estimate

    for b, r in _buckets(config):
        def s(shape, dt=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        batch = ColumnBatch(
            chunk_S=s((b, r)), chunk_rows=s((b, r)), chunk_nulls=s((b, r)),
            chunk_dict_encoded=s((b, r), jnp.bool_), N=s((b,)),
            nulls=s((b,)), n_groups=s((b,), jnp.int32), mins=s((b, r)),
            maxs=s((b, r)), valid=s((b, r), jnp.bool_), m_min=s((b,)),
            m_max=s((b,)), mean_len=s((b,)), len_sample=s((b,), jnp.int32),
            fixed_width=s((b,), jnp.bool_), int_like=s((b,), jnp.bool_),
            single_byte=s((b,), jnp.bool_))
        # The served path on a TPU: `estimate_batch` -> `fused_estimate`.
        text = jax.jit(lambda x, sb: fused_estimate(
            x, sb, mode="paper", interpret=False)).lower(
                batch, s((b,))).compile().as_text()
        assert "tpu_custom_call" in text, (b, r)


@pytest.mark.parametrize("config", ["tpcds_sf1000", "tpch_sf1000"])
def test_planner_scans_compile(one_chip, config):
    import jax
    import jax.numpy as jnp

    from repro.planner.cost import _scan_fold

    for n, p in _plan_shapes(config):
        rows = jax.ShapeDtypeStruct((p, n), jnp.float32, sharding=one_chip)
        _scan_fold(n, p).lower(rows, rows).compile()
