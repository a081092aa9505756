"""One replica per chip: the fleet's placement on a four-device host.

`Fleet` divides the local devices among each dataset's replicas
(`replica_devices`). XLA fixes the device count at process start, so the
checks run once in a subprocess with four simulated CPU devices and report
each result by name; every test below asserts one of them:

  * four replicas: each replica's engine owns one device, resolves
    ``local``, keeps its resident packs and its dispatches there, and has
    compiled its dataset's bucket there when it started
  * one replica: it keeps all four devices and still resolves ``sharded``
  * bodies and ETags of /estimate, /tablestats and /cost are byte-identical
    between a one-replica and a four-replica fleet, and the estimates equal
    ``backend="ref"``
  * a refresh acknowledged through the router is in every replica's next
    answer
  * /metrics carries the dispatch counter's ``device`` label and the
    ``fleet.refresh_broadcast`` span
"""
import json
import os
import subprocess
import sys

import pytest

from repro.fleet import replica_devices

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    "--xla_cpu_multi_thread_eigen=false"
)
import json
import tempfile
import urllib.request

import jax
import numpy as np

from repro import obs
from repro.catalog import StatsCatalog
from repro.catalog.source import InMemoryMetadataSource
from repro.columnar.writer import WriterOptions, write_file
from repro.engine import EngineConfig, EstimationEngine
from repro.fleet import DatasetRegistry, Fleet, StatsRequest, StatsRouter
from repro.service import fetch_json
from repro.wire import fetch

devices = jax.local_devices()
assert len(devices) == 4, devices
base = tempfile.mkdtemp()
out = {}
compiles = [0]


def count_compiles(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(count_compiles)


def check(name, ok, detail=None):
    out[name] = [bool(ok), detail]


def footers(name, seeds):
    got = {}
    for seed in seeds:
        rng = np.random.default_rng(seed)
        got[f"{name}/shard_{seed:03d}"] = write_file(
            os.path.join(base, "files", name, f"shard_{seed:03d}"),
            {
                "tok": rng.integers(0, 40 + seed, 512).astype(np.int64),
                "val": np.round(rng.uniform(0, 100, 512), 1),
            },
            options=WriterOptions(row_group_size=128),
        )
    return got


FOOTERS = {"orders": footers("orders", (10, 11)),
           "lines": footers("lines", (20, 21, 22))}
NEW = footers("orders", (12,))


def fleet(replicas):
    # Each fleet spills to its own directory: the four-replica fleet must
    # compute, not read the one-replica fleet's estimates back.
    reg = DatasetRegistry()
    sources = {}
    for name, fs in FOOTERS.items():
        root = os.path.join(base, f"spill{replicas}", name)
        os.makedirs(root)
        sources[name] = InMemoryMetadataSource(fs, root=root)
        reg.add("wh", name, sources[name])
    router = StatsRouter(Fleet(reg, replicas_per_dataset=replicas)).start()
    return router, sources


def dispatches_by_device():
    counts = {}
    for labels, cell in obs.registry().counter(
            "ndv_engine_dispatches_total").snapshot():
        dev = dict(labels).get("device")
        counts[dev] = counts.get(dev, 0) + cell.value
    return counts


def body_key(status, etag, body):
    return [status, etag, json.dumps(body, sort_keys=True)]


GRAPH = {
    "tables": [
        {"name": "o", "namespace": "wh", "dataset": "orders"},
        {"name": "l", "namespace": "wh", "dataset": "lines",
         "filter_selectivity": 0.5},
    ],
    "edges": [{"left": "o", "left_column": "tok",
               "right": "l", "right_column": "tok"}],
}


def served(router):
    got = {}
    for name in FOOTERS:
        for mode in ("paper", "improved"):
            got[f"{name}/estimate/{mode}"] = body_key(*fetch_json(
                router.url_for("wh", name, "estimate") + f"?mode={mode}"))
        got[f"{name}/tablestats"] = body_key(*fetch_json(
            router.url_for("wh", name, "tablestats")))
    got["cost"] = body_key(*fetch(router.url + "/cost",
                                  payload={"graph": GRAPH}, binary=False))
    return got


# -- one replica keeps every device and shards --------------------------------
one, _ = fleet(1)
for key, rset in one.fleet.sets.items():
    (rep,) = rset.replicas
    eng = rep.service.engine
    width = rep.service.catalog.packed_batch().batch
    check(f"one_replica_all_devices/{key}",
          eng.devices == tuple(devices), [d.id for d in eng.devices])
    check(f"one_replica_sharded/{key}",
          eng.resolve_strategy(width) == "sharded",
          eng.resolve_strategy(width))
bodies_one = served(one)

# -- four replicas, one device each -------------------------------------------
four, sources = fleet(4)
# Each replica warmed its bucket on its own device when it started: its
# first estimate compiles nothing.
for key, rset in four.fleet.sets.items():
    for i, rep in enumerate(rset.replicas):
        n = compiles[0]
        rep.service.catalog.estimate(mode="paper")
        check(f"warm_no_compile/{key}#{i}", compiles[0] == n, compiles[0] - n)
before = dispatches_by_device()
for key, rset in four.fleet.sets.items():
    for i, rep in enumerate(rset.replicas):
        eng = rep.service.engine
        check(f"replica_device/{key}#{i}", eng.devices == (devices[i],),
              [d.id for d in eng.devices])
        # A bound of its own: a cold request every replica computes itself
        # rather than reading a sibling's spill.
        resp = rep.handle(StatsRequest(
            "estimate", "paper", schema_bounds=(("tok", 1000.0 + i),)))
        batch = rep.service.catalog.packed_batch()
        check(f"replica_local/{key}#{i}",
              resp.status == 200
              and eng.resolve_strategy(batch.batch) == "local",
              eng.resolve_strategy(batch.batch))
        placed = {d.id for leaf in jax.tree.leaves(batch)
                  for d in leaf.devices()}
        check(f"packs_on_own_device/{key}#{i}",
              placed == {devices[i].id} and all(
                  leaf.committed for leaf in jax.tree.leaves(batch)),
              sorted(placed))
after = dispatches_by_device()
grew = {d: after.get(d, 0) - before.get(d, 0) for d in after}
check("dispatches_on_every_device",
      all(grew.get(str(d.id), 0) == len(FOOTERS) for d in devices)
      and sum(grew.values()) == 4 * len(FOOTERS), grew)

bodies_four = served(four)
for name in bodies_one:
    check(f"identical/{name}", bodies_one[name] == bodies_four[name],
          [bodies_one[name][:2], bodies_four[name][:2]])

for name, fs in FOOTERS.items():
    for mode in ("paper", "improved"):
        ref = StatsCatalog(
            InMemoryMetadataSource(fs),
            engine=EstimationEngine(EngineConfig(backend="ref",
                                                 strategy="local")),
        ).estimate(mode=mode)
        got = json.loads(bodies_four[f"{name}/estimate/{mode}"][2])
        check(f"equals_ref/{name}/{mode}",
              {c: e["ndv"] for c, e in got["estimates"].items()}
              == {c: float(e.ndv) for c, e in ref.items()},
              None)

# -- a refresh acknowledged through the router reaches every replica ----------
rset = four.fleet.sets["wh/orders"]
old = [rep.handle(StatsRequest("tablestats")) for rep in rset.replicas]
for fid, footer in NEW.items():
    sources["orders"].add(fid, footer)
status, _, ack = fetch_json(four.url + "/wh/orders/refresh", method="POST")
new = [rep.handle(StatsRequest("tablestats")) for rep in rset.replicas]
check("refresh_reaches_every_replica",
      status == 200 and len(ack["refreshed"]["wh/orders"]) == 4
      and all(s["added"] == 1 for s in ack["refreshed"]["wh/orders"].values())
      and len({r.etag for r in new}) == 1
      and all(n.etag != o.etag and n.body["generation"] == o.body["generation"] + 1
              for n, o in zip(new, old)),
      [[o.body["generation"], n.body["generation"]] for o, n in zip(old, new)])

with urllib.request.urlopen(four.url + "/metrics") as r:
    text = r.read().decode()
lines = text.splitlines()
check("metrics_device_label",
      all(any(l.startswith("ndv_engine_dispatches_total{")
              and f'device="{d.id}"' in l for l in lines) for d in devices),
      [l for l in lines if l.startswith("ndv_engine_dispatches_total{")])
check("metrics_refresh_broadcast_span",
      any('span="fleet.refresh_broadcast"' in l for l in lines), None)

one.stop()
four.stop()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + os.path.join(HERE, "..")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def _assert_all(results, prefix):
    named = {k: v for k, v in results.items() if k.startswith(prefix)}
    assert named, f"no check named {prefix}*"
    failed = {k: v[1] for k, v in named.items() if not v[0]}
    assert not failed, failed


def test_four_replicas_each_own_one_device(results):
    _assert_all(results, "replica_device/")


def test_four_replicas_resolve_local(results):
    _assert_all(results, "replica_local/")


def test_four_replicas_keep_packs_on_their_device(results):
    _assert_all(results, "packs_on_own_device/")


def test_four_replicas_dispatch_on_their_device(results):
    _assert_all(results, "dispatches_on_every_device")


def test_four_replicas_warm_their_bucket_at_start(results):
    _assert_all(results, "warm_no_compile/")


def test_one_replica_keeps_every_device_and_shards(results):
    _assert_all(results, "one_replica_all_devices/")
    _assert_all(results, "one_replica_sharded/")


@pytest.mark.parametrize("endpoint", ["estimate", "tablestats", "cost"])
def test_bodies_and_etags_identical_to_one_replica(results, endpoint):
    named = [k for k in results if k.startswith("identical/")
             and endpoint in k]
    assert named
    for k in named:
        assert results[k][0], (k, results[k][1])


def test_four_replica_estimates_equal_the_reference_backend(results):
    _assert_all(results, "equals_ref/")


def test_acknowledged_refresh_is_in_every_replicas_next_answer(results):
    _assert_all(results, "refresh_reaches_every_replica")


def test_metrics_carry_device_label_and_broadcast_span(results):
    _assert_all(results, "metrics_device_label")
    _assert_all(results, "metrics_refresh_broadcast_span")


@pytest.mark.parametrize("replicas,expect", [
    (1, [(0, 1, 2, 3)]),
    (2, [(0, 2), (1, 3)]),
    (3, [(0, 3), (1,), (2,)]),
    (4, [(0,), (1,), (2,), (3,)]),
    (6, [(0,), (1,), (2,), (3,), (0,), (1,)]),
])
def test_replica_devices_divides_the_host(replicas, expect):
    devices = [0, 1, 2, 3]
    assert [replica_devices(i, replicas, devices)
            for i in range(replicas)] == expect
