"""EstimationEngine: strategy parity, shard-aware packing, cache keying,
and estimate-cache persistence.

The engine's contract is bit-for-bit parity across execution strategies for
real (non-padding) lanes. `test_strategy_parity_matrix` is the CI parity
matrix selector: the workflow runs it once per (strategy, device count)
cell via ``-k "parity_matrix and <strategy>"`` under
``XLA_FLAGS=--xla_force_host_platform_device_count={1,4}``, so a parity
break names the exact strategy/topology that diverged. Sharded parity on
>= 4 devices additionally runs in a subprocess (XLA device count is fixed
at process start); when the host process itself has >= 4 simulated devices
the in-process variants run too.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.catalog import BatchPacker, StatsCatalog
from repro.core import estimate_columns
from repro.core.ndv.types import ColumnMetadata, PhysicalType
from repro.engine import EngineConfig, EstimationEngine, default_engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _column(seed: int, r: int, name: str = "c") -> ColumnMetadata:
    rng = np.random.default_rng(seed)
    mins = np.sort(rng.uniform(0, 1e5, r))
    return ColumnMetadata(
        chunk_sizes=rng.uniform(2_000.0, 90_000.0, r),
        chunk_rows=np.full(r, 4096.0),
        chunk_nulls=rng.integers(0, 64, r).astype(np.float64),
        chunk_dict_encoded=rng.uniform(size=r) > 0.2,
        mins=mins,
        maxs=mins + rng.uniform(10.0, 1e4, r),
        min_lengths=np.full(r, 8.0),
        max_lengths=np.full(r, 8.0),
        distinct_min_count=float(max(r - 1, 1)),
        distinct_max_count=float(r),
        physical_type=PhysicalType.INT64,
        column_name=f"{name}{seed}",
    )


def _columns(width: int):
    # Ragged row-group counts: exercises padding in both axes.
    return [_column(i, r=1 + (i % 7)) for i in range(width)]


# -- strategy×device parity matrix (the CI selector) --------------------------


@pytest.mark.parametrize("strategy", ["local", "sharded", "chunked", "composed"])
def test_strategy_parity_matrix(strategy):
    """One cell of the CI parity matrix: `strategy` vs local, bit for bit.

    Runs at whatever device count the process was started with (the CI
    matrix forces 1 and 4 via XLA_FLAGS) — every strategy must hold parity
    on every topology, including the degenerate single-device mesh.
    """
    ref_engine = EstimationEngine(EngineConfig(strategy="local"))
    eng = EstimationEngine(EngineConfig(strategy=strategy, max_batch=8))
    # Widths straddling the mesh-wide budget, plus one below the shard count.
    for width in (3, 13, 64):
        cols = _columns(width)
        bounds = [np.inf] * width
        bounds[width // 2] = 5.0
        for mode in ("paper", "improved"):
            ref, ref_prov = ref_engine.estimate_columns_explained(
                cols, bounds, mode=mode
            )
            got, got_prov = eng.estimate_columns_explained(
                cols, bounds, mode=mode
            )
            assert got == ref, (strategy, width, mode)
            # Provenance rides the same lanes through the same execution
            # plans: diagnostics must hold the parity contract too, or an
            # explained response would change with the serving topology.
            assert got_prov == ref_prov, (strategy, width, mode)


@pytest.mark.parametrize(
    "strategy", ["local", "composed"], ids=["fused_local", "fused_composed"]
)
def test_fused_parity_matrix(strategy):
    """Fused cells of the CI parity matrix: fuse=on vs fuse=off, bit for bit.

    The fuse knob stays out of engine cache identity, so it must be
    numerics-invisible on every strategy/topology the matrix runs
    (`-k "parity_matrix and fused_<strategy>"` under 1 and 4 simulated
    devices). Local and composed bracket the strategy space: single jit
    call vs mesh-split + per-shard chunk streaming.
    """
    on = EstimationEngine(EngineConfig(strategy=strategy, max_batch=8, fuse="on"))
    off = EstimationEngine(EngineConfig(strategy=strategy, max_batch=8, fuse="off"))
    assert on.cache_key == off.cache_key
    assert on.cache_token == off.cache_token
    for width in (3, 13, 64):
        cols = _columns(width)
        bounds = [np.inf] * width
        bounds[width // 2] = 5.0
        for mode in ("paper", "improved"):
            ref, ref_prov = off.estimate_columns_explained(
                cols, bounds, mode=mode
            )
            got, got_prov = on.estimate_columns_explained(
                cols, bounds, mode=mode
            )
            assert got == ref, (strategy, width, mode)
            assert got_prov == ref_prov, (strategy, width, mode)


# -- chunked parity (any device count) ---------------------------------------


@pytest.mark.parametrize("mode", ["paper", "improved"])
@pytest.mark.parametrize("width", [5, 13, 64])
def test_chunked_matches_local_bit_for_bit(mode, width):
    cols = _columns(width)
    local = EstimationEngine(EngineConfig(strategy="local"))
    chunked = EstimationEngine(EngineConfig(strategy="chunked", max_batch=8))
    ref = local.estimate_columns(cols, mode=mode)
    got = chunked.estimate_columns(cols, mode=mode)
    assert got == ref  # NDVEstimate equality is exact float equality


def test_chunked_with_schema_bounds_matches_local():
    cols = _columns(20)
    bounds = [np.inf] * 20
    bounds[3] = 7.0
    bounds[17] = 2.0
    local = EstimationEngine(EngineConfig(strategy="local"))
    chunked = EstimationEngine(EngineConfig(strategy="chunked", max_batch=8))
    ref = local.estimate_columns(cols, bounds)
    got = chunked.estimate_columns(cols, bounds)
    assert got == ref
    assert got[3].ndv <= 7.0 and got[17].ndv <= 2.0


# -- sharded parity -----------------------------------------------------------

_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    "--xla_cpu_multi_thread_eigen=false"
)
import json
import numpy as np
import jax

from tests.test_engine import _columns
from repro.engine import EngineConfig, EstimationEngine

assert jax.device_count() >= 4, jax.device_count()
out = {"devices": jax.device_count(), "ok": True, "fail": []}
for width in (3, 13, 64):          # 3 < shards: pure padding lanes on 3 shards
    cols = _columns(width)
    for mode in ("paper", "improved"):
        local = EstimationEngine(EngineConfig(strategy="local"))
        sharded = EstimationEngine(EngineConfig(strategy="sharded"))
        chunked = EstimationEngine(EngineConfig(strategy="chunked", max_batch=8))
        composed = EstimationEngine(EngineConfig(strategy="composed", max_batch=4))
        ref = local.estimate_columns(cols, mode=mode)
        for name, eng in (
            ("sharded", sharded), ("chunked", chunked), ("composed", composed)
        ):
            got = eng.estimate_columns(cols, mode=mode)
            if got != ref:
                out["ok"] = False
                out["fail"].append([name, mode, width])

# auto resolves to composed when both multi-device and over-budget hold,
# and the composed result still matches local bit for bit.
auto = EstimationEngine(EngineConfig(strategy="auto", max_batch=4))
cols = _columns(64)
batch = auto.make_packer().pack(cols)
resolved = auto.resolve_strategy(batch.batch)
if resolved != "composed":
    out["ok"] = False
    out["fail"].append(["auto-resolution", resolved, batch.batch])
ref = EstimationEngine(EngineConfig(strategy="local")).estimate_columns(cols)
if auto.estimate_columns(cols) != ref:
    out["ok"] = False
    out["fail"].append(["auto-composed-parity", "paper", 64])
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_parity_on_simulated_devices():
    """Bit-equality of sharded/chunked vs local on 4 simulated CPU devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        SRC + os.pathsep + os.path.join(os.path.dirname(__file__), "..")
    )
    res = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] >= 4
    assert out["ok"], out["fail"]


@pytest.mark.skipif(
    jax.device_count() < 4, reason="needs >= 4 devices (CI parity step)"
)
@pytest.mark.parametrize("mode", ["paper", "improved"])
def test_sharded_matches_local_in_process(mode):
    cols = _columns(13)
    ref = EstimationEngine(EngineConfig(strategy="local")).estimate_columns(
        cols, mode=mode
    )
    got = EstimationEngine(EngineConfig(strategy="sharded")).estimate_columns(
        cols, mode=mode
    )
    assert got == ref


# -- composed strategy ---------------------------------------------------------


def test_composed_plan_shapes():
    from repro.engine import composed_plan

    # wider than one super-chunk: whole super-chunks, equal spans
    padded, spans = composed_plan(100, 3, 4)
    assert padded == 108 and padded % (3 * 4) == 0
    assert spans == [(lo, lo + 12) for lo in range(0, 108, 12)]
    # fits one dispatch: pad only to the shard count, not a full super-chunk
    assert composed_plan(5, 3, 4) == (6, [(0, 6)])
    assert composed_plan(8, 4, 1024) == (8, [(0, 8)])
    with pytest.raises(ValueError, match="positive"):
        composed_plan(0, 1, 1)


def test_composed_matches_local_any_device_count():
    # Parity must hold even on the degenerate 1-device mesh (CPU default):
    # composed then reduces to pure chunk streaming.
    cols = _columns(37)
    local = EstimationEngine(EngineConfig(strategy="local"))
    comp = EstimationEngine(EngineConfig(strategy="composed", max_batch=8))
    for mode in ("paper", "improved"):
        assert comp.estimate_columns(cols, mode=mode) == local.estimate_columns(
            cols, mode=mode
        )


def test_auto_resolves_composed_when_multi_device_and_over_budget(monkeypatch):
    eng = EstimationEngine(EngineConfig(strategy="auto", max_batch=8))
    monkeypatch.setattr(
        EstimationEngine, "shard_count", property(lambda self: 4)
    )
    # over the mesh-wide budget (4 shards x 8) -> composed
    assert eng.resolve_strategy(64) == "composed"
    # at or under it -> plain sharded
    assert eng.resolve_strategy(32) == "sharded"
    assert eng.resolve_strategy(4) == "sharded"


@pytest.mark.skipif(
    jax.device_count() < 4, reason="needs >= 4 devices (CI parity step)"
)
def test_auto_resolves_composed_in_process():
    eng = EstimationEngine(EngineConfig(strategy="auto", max_batch=4))
    batch = eng.make_packer().pack(_columns(64))
    assert eng.resolve_strategy(batch.batch) == "composed"
    ref = EstimationEngine(EngineConfig(strategy="local")).estimate_columns(
        _columns(64)
    )
    assert eng.estimate_columns(_columns(64)) == ref


def test_composed_packer_coordinates_shards_and_chunks(monkeypatch):
    monkeypatch.setattr(
        EstimationEngine, "shard_count", property(lambda self: 3)
    )
    eng = EstimationEngine(EngineConfig(strategy="composed", max_batch=4))
    packer = eng.make_packer()
    assert packer.col_multiple == 3 and packer.col_chunk == 4
    # bucket(37) = 64 > one super-chunk (12) -> whole super-chunks
    assert packer.shape_for(37, 4)[0] == 72
    # narrow batch: multiple of shards only, NOT padded to a super-chunk
    assert packer.shape_for(5, 4)[0] == 9  # bucket 8 -> next multiple of 3


def test_shard_clamp_is_surfaced_once(caplog):
    n_dev = jax.device_count()
    eng = EstimationEngine(
        EngineConfig(strategy="sharded", num_shards=n_dev + 60)
    )
    with caplog.at_level("WARNING", logger="repro.engine.engine"):
        assert eng.shard_count == n_dev
        assert eng.shard_count == n_dev  # second read: no duplicate log
    clamps = [r for r in caplog.records if "clamping" in r.message]
    assert len(clamps) == 1
    assert str(n_dev + 60) in clamps[0].getMessage()
    # a satisfiable config never logs
    caplog.clear()
    with caplog.at_level("WARNING", logger="repro.engine.engine"):
        assert EstimationEngine(
            EngineConfig(strategy="sharded", num_shards=n_dev)
        ).shard_count == n_dev
    assert not [r for r in caplog.records if "clamping" in r.message]


# -- packer shard-awareness ----------------------------------------------------


def test_packer_col_multiple_rounds_up_evenly():
    packer = BatchPacker(col_multiple=3)
    cols = _columns(4)
    batch = packer.pack(cols)
    assert batch.batch % 3 == 0
    assert batch.batch == 6  # bucket(4) = 4 -> next multiple of 3
    # padding lanes fully masked
    assert not np.asarray(batch.valid)[4:].any()
    assert (np.asarray(batch.n_groups)[4:] == 0).all()


def test_engine_packer_matches_shard_count():
    eng = EstimationEngine(EngineConfig(strategy="sharded"))
    packer = eng.make_packer()
    assert packer.col_multiple == eng.shard_count
    batch = packer.pack(_columns(5))
    assert batch.batch % eng.shard_count == 0


def test_backend_values_agree_on_clean_data():
    # pallas (interpret) vs ref run different iteration counts; on
    # well-conditioned synthetic columns all backends converge to the
    # same estimates within float tolerance.
    cols = _columns(4)
    ref = EstimationEngine(EngineConfig(backend="ref")).estimate_columns(cols)
    auto = EstimationEngine(EngineConfig(backend="auto")).estimate_columns(cols)
    assert auto == ref  # off-TPU, auto IS the reference path
    pallas = EstimationEngine(
        EngineConfig(backend="pallas")
    ).estimate_columns(cols)
    for a, b in zip(pallas, ref):
        assert a.ndv == pytest.approx(b.ndv, rel=1e-3)
        assert a.layout == b.layout


def test_estimate_columns_uses_shared_default_packer():
    from repro.engine import default_packer

    ests = estimate_columns(_columns(3))
    assert len(ests) == 3
    assert default_packer() is default_packer()  # one instance per process
    assert default_engine() is default_engine()


def test_engine_config_validation():
    with pytest.raises(ValueError, match="strategy"):
        EngineConfig(strategy="turbo")
    with pytest.raises(ValueError, match="power of two"):
        EngineConfig(max_batch=3)
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="cuda")


# -- catalog integration -------------------------------------------------------


def _dataset(tmp_path, n_files=2):
    from repro.columnar import write_file
    from repro.columnar.writer import WriterOptions

    rng = np.random.default_rng(0)
    for i in range(n_files):
        write_file(
            str(tmp_path / f"shard_{i:03d}"),
            {
                "tok": rng.integers(0, 64, 512).astype(np.int64),
                "val": np.round(rng.uniform(0, 100, 512), 1),
            },
            options=WriterOptions(row_group_size=128),
        )
    return str(tmp_path)


def test_catalog_cache_shared_across_strategies_split_by_backend(tmp_path):
    # The neutrality rules: strategy / shard count / chunk budget are
    # numerics-neutral (parity contract), so engines differing only in them
    # SHARE a cache line — a strategy change invalidates nothing. Only the
    # backend can change numerics, so it still splits entries.
    root = _dataset(tmp_path)
    catalog = StatsCatalog(root)
    e_local = EstimationEngine(EngineConfig(strategy="local"))
    e_chunked = EstimationEngine(EngineConfig(strategy="chunked", max_batch=2))
    e_composed = EstimationEngine(
        EngineConfig(strategy="composed", max_batch=2, num_shards=1)
    )

    first = catalog.estimate(engine=e_local)
    assert catalog.stats.estimate_cache_misses == 1
    # same config, different engine instance -> cache hit (config is the key)
    again = catalog.estimate(engine=EstimationEngine(EngineConfig(strategy="local")))
    assert catalog.stats.estimate_cache_hits == 1
    assert again == first
    # different execution shape, same numerics -> same entry stays warm
    assert catalog.estimate(engine=e_chunked) == first
    assert catalog.estimate(engine=e_composed) == first
    assert catalog.stats.estimate_cache_hits == 3
    assert catalog.stats.estimate_cache_misses == 1
    # a different backend is a different numeric identity -> separate entry
    catalog.estimate(engine=EstimationEngine(EngineConfig(backend="ref")))
    assert catalog.stats.estimate_cache_misses == 2


def test_catalog_estimates_match_direct_engine_call(tmp_path):
    root = _dataset(tmp_path)
    engine = EstimationEngine(EngineConfig(strategy="chunked", max_batch=2))
    catalog = StatsCatalog(root, engine=engine)
    got = catalog.estimate(mode="improved")
    merged = catalog.merged_metadata()
    cols = [merged[n] for n in catalog.column_names]
    ref = {
        e.column_name: e
        for e in engine.estimate_columns(cols, mode="improved")
    }
    assert got == ref


def test_save_load_cache_round_trip(tmp_path):
    root = _dataset(tmp_path)
    catalog = StatsCatalog(root)
    warm = catalog.estimate(mode="improved")
    catalog.estimate(mode="paper")
    path = catalog.save_cache()
    assert os.path.exists(path)

    # fresh catalog (a restart): loads the spilled entries, serves without
    # re-estimating
    restarted = StatsCatalog(root)
    assert restarted.load_cache() == 2
    got = restarted.estimate(mode="improved")
    assert restarted.stats.estimate_cache_hits == 1
    assert restarted.stats.estimate_cache_misses == 0
    assert restarted.stats.packs == 0
    assert got == warm  # bit-identical through the JSON round trip


def test_load_cache_misses_on_changed_dataset(tmp_path):
    from repro.columnar import write_file
    from repro.columnar.writer import WriterOptions

    root = _dataset(tmp_path)
    catalog = StatsCatalog(root)
    catalog.estimate()
    catalog.save_cache()

    rng = np.random.default_rng(9)
    write_file(
        str(tmp_path / "shard_099"),
        {
            "tok": rng.integers(0, 64, 512).astype(np.int64),
            "val": np.round(rng.uniform(0, 100, 512), 1),
        },
        options=WriterOptions(row_group_size=128),
    )
    restarted = StatsCatalog(root)
    assert restarted.load_cache() == 1
    restarted.estimate()  # new fingerprint set -> stale entry unreachable
    assert restarted.stats.estimate_cache_misses == 1


def test_load_cache_missing_file_is_cold_start(tmp_path):
    root = _dataset(tmp_path)
    catalog = StatsCatalog(root)
    assert catalog.load_cache() == 0


def test_save_cache_requires_root_for_memory_sources():
    from repro.catalog import InMemoryMetadataSource

    catalog = StatsCatalog(InMemoryMetadataSource({}))
    with pytest.raises(ValueError, match="root"):
        catalog.save_cache()


def test_pipeline_engine_config_threads_through(tmp_path):
    from repro.data.pipeline import DataConfig, TokenPipeline, synthesize_token_dataset

    root = str(tmp_path / "ds")
    synthesize_token_dataset(root, num_shards=1, rows_per_shard=1 << 12)
    cfg = DataConfig(
        root=root,
        engine=EngineConfig(strategy="chunked", max_batch=2),
    )
    pipe = TokenPipeline(cfg)
    assert pipe.catalog.engine.config.strategy == "chunked"
    assert pipe.plan.estimates  # planned through the chunked engine


# -- "auto" chunk budget -------------------------------------------------------


def test_auto_chunk_budget_math():
    from repro.engine import DEFAULT_MAX_BATCH, auto_chunk_budget
    from repro.engine.engine import (
        AUTO_MAX_BATCH,
        AUTO_MEM_FRACTION,
        AUTO_MIN_BATCH,
        NOMINAL_LANE_BYTES,
    )

    # no memory report -> historical constant
    assert auto_chunk_budget(None) == DEFAULT_MAX_BATCH
    assert auto_chunk_budget(0) == DEFAULT_MAX_BATCH
    # 16 GiB at the documented fraction and lane footprint, floor-pow2
    want = int(16 * 2**30 * AUTO_MEM_FRACTION / NOMINAL_LANE_BYTES)
    got = auto_chunk_budget(16 * 2**30)
    assert got == 1 << (want.bit_length() - 1) == 65536
    # clamps on both ends, always a power of two
    assert auto_chunk_budget(1) == AUTO_MIN_BATCH
    assert auto_chunk_budget(1 << 60) == AUTO_MAX_BATCH
    for mem in (2**28, 2**31, 7 * 10**9):
        b = auto_chunk_budget(mem)
        assert b & (b - 1) == 0 and AUTO_MIN_BATCH <= b <= AUTO_MAX_BATCH


def test_auto_budget_shrinks_per_shard(monkeypatch):
    # The composed per-shard budget divides the memory report across the
    # mesh (simulated host devices all report the one shared pool), so the
    # budget shrinks as the mesh grows — and the report is read only once
    # per engine no matter how many shard counts are resolved.
    from repro.engine import engine as engine_mod

    calls = []

    def fake_detect(device=None):
        calls.append(1)
        return 16 * 2**30

    monkeypatch.setattr(engine_mod, "detect_device_memory", fake_detect)
    eng = EstimationEngine(EngineConfig(strategy="composed", max_batch="auto"))
    assert eng.resolve_max_batch() == 65536
    assert eng.resolve_max_batch(shards=4) == 65536 // 4
    assert eng.resolve_max_batch(shards=3) == 16384  # pow2 floor of /3
    assert len(calls) == 1


def test_engine_config_auto_max_batch_validation():
    assert EngineConfig(max_batch="auto").max_batch == "auto"
    with pytest.raises(ValueError, match="auto"):
        EngineConfig(max_batch="turbo")


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self.device_kind = f"fake {platform}"
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


@pytest.mark.parametrize(
    "platform,stats,expect",
    [
        ("cpu", None, None),
        ("tpu", {"bytes_limit": 16 * 2**30}, 16 * 2**30),
        ("tpu", {"bytes_reservable_limit": 2**33}, 2**33),
        ("tpu", None, RuntimeError),
        ("tpu", {}, RuntimeError),
        ("tpu", OSError("allocator gone"), OSError),
    ],
)
def test_detect_device_memory_never_hides_an_accelerator(
    monkeypatch, platform, stats, expect
):
    # Only the host CPU may report "unknown"; on an accelerator a failed or
    # empty report raises instead of shrinking the auto budget.
    from repro.engine import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.jax, "local_devices", lambda: [_FakeDevice(platform, stats)]
    )
    if isinstance(expect, type) and issubclass(expect, Exception):
        with pytest.raises(expect):
            engine_mod.detect_device_memory()
    else:
        assert engine_mod.detect_device_memory() == expect


def test_resolve_max_batch_auto_detects_once(monkeypatch):
    from repro.engine import engine as engine_mod

    calls = []

    def fake_detect(device=None):
        calls.append(1)
        return 16 * 2**30

    monkeypatch.setattr(engine_mod, "detect_device_memory", fake_detect)
    eng = EstimationEngine(EngineConfig(strategy="chunked", max_batch="auto"))
    assert eng.resolve_max_batch() == 65536
    assert eng.resolve_max_batch() == 65536
    assert len(calls) == 1  # detection is cached per engine
    # a fixed budget never consults the device
    calls.clear()
    fixed = EstimationEngine(EngineConfig(max_batch=128))
    assert fixed.resolve_max_batch() == 128 and not calls


def test_engine_identity_is_backend_only():
    # The execution shape (strategy, shards, chunk budget — resolved or
    # not) is numerics-neutral, so none of it may leak into cache keys or
    # ETag material: a spill written on a big-memory host under "local"
    # stays warm on a small sharded mesh, and a client cache survives a
    # server-side strategy change.
    for cfg in (
        EngineConfig(strategy="chunked", max_batch="auto"),
        EngineConfig(strategy="composed", max_batch=128, num_shards=8),
        EngineConfig(strategy="local"),
    ):
        eng = EstimationEngine(cfg)
        assert eng.cache_key == ("auto",)
        assert eng.cache_token == "k.ref"  # resolved backend, nothing else
    assert EstimationEngine(EngineConfig(backend="ref")).cache_key == ("ref",)


def test_auto_budget_chunked_parity_with_local():
    local = EstimationEngine(EngineConfig(strategy="local"))
    auto = EstimationEngine(EngineConfig(strategy="chunked", max_batch="auto"))
    auto._auto_budgets = {1: 2}  # force real chunking at test width
    cols = _columns(7)
    packer = BatchPacker()
    batch = packer.pack(cols)
    for mode in ("paper", "improved"):
        ref = local.estimate(batch, mode=mode)
        got = auto.estimate(batch, mode=mode)
        for f_ref, f_got in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_got))


# -- warm-up ---------------------------------------------------------------------


@pytest.mark.parametrize("width,groups", [(1, 1), (5, 9), (20, 7)])
def test_padding_batch_matches_the_pack_bucket(width, groups):
    packer = BatchPacker()
    cols = [_column(i, r=groups if i == 0 else 1) for i in range(width)]
    real = packer.pack(cols)
    pad = packer.padding_batch(width, groups)
    assert [(x.shape, x.dtype, x.weak_type) for x in jax.tree.leaves(pad)] \
        == [(x.shape, x.dtype, x.weak_type) for x in jax.tree.leaves(real)]
    assert not np.asarray(pad.valid).any()


def test_warm_compiles_the_bucket_a_real_estimate_then_reuses():
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    # A bucket no other test dispatches: 96 columns of up to 48 row groups.
    cols = [_column(i, r=48 if i == 0 else 3) for i in range(70)]
    ref = EstimationEngine(EngineConfig(strategy="local", backend="ref"))
    want = ref.estimate_columns(cols, mode="improved")
    eng = EstimationEngine(EngineConfig(strategy="local"))
    compiles.clear()
    eng.warm(len(cols), 48, mode="improved")
    assert compiles
    compiles.clear()
    assert eng.estimate_columns(cols, mode="improved") == want
    assert compiles == []
