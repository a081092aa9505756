"""Fleet tier: replicated, multi-dataset stats serving with one router.

One `StatsServer` fronts one dataset; a planner fleet polls a whole
warehouse namespace. This package is the tier in between — N interchangeable
replicas per dataset, a registry of datasets, and a single stdlib-HTTP
router that any client can treat as "the warehouse":

                          StatsRouter (HTTP)
          /datasets  /health  /{ns}/{ds}/{columns|estimate|plan}  /refresh
                                   |
                                 Fleet ---------------- DatasetRegistry
                  (routing, counters, health prober)    ns/ds -> root +
                           |                 |          EngineConfig
                  ReplicaSet "ns/a"   ReplicaSet "ns/b"
                  rendezvous hashing over (dataset, request identity);
                  eject on failure, retry next, rejoin on probe
                   |           |           |           |
               LocalReplica LocalReplica  ...    RemoteReplica
               StatsService StatsService         (HTTP proxy to a
                   \\          /                   StatsServer)
                .ndv_estimate_cache.json
                (shared on-disk estimate spill: atomic merge-not-
                 clobber writes; a cold replica's first estimate is
                 a cache hit, zero engine packs)

Why replicas are interchangeable — the invariant everything rests on:
response ETags are SHA-1 over (dataset fingerprint set, engine cache
token, request identity) and nothing else. The registry pins one
`EngineConfig` per dataset, every replica ingests the same files, so two
independently-constructed replicas emit byte-identical tags. Failover is
therefore invisible to clients: a revalidation that lands on a different
replica than the one that minted the tag still returns 304, and a replica
that crashes mid-burst costs one retry, not a cache flush.

Placement is rendezvous (highest-random-weight) hashing: identical
requests always land on the same healthy replica (maximizing its estimate
cache), distinct identities spread across the set, and an ejection moves
only the ejected replica's keys. Cold starts ride the shared spill:
replicas run `StatsService(shared_spill=True)`, so every computed entry is
merged into the dataset's on-disk cache file and a freshly booted replica
loads it before serving.

Devices: `Fleet` divides the host's local devices among each dataset's
replicas (`replica_devices`): with at least as many replicas as chips,
replica i runs on chip i mod n, its packs resident there and its engine
local to it; a single replica keeps every chip and shards across them.
Replica i of every dataset shares a chip. A refresh is broadcast to the
replicas one after another (`fleet.refresh_broadcast` span) and answered
only when every replica has ingested it. The planner behind `/cost` runs
in the router, on the default device.

Batched RPC: the router's `POST /batch` accepts tuples spanning any mix of
registered datasets in one frame (JSON or the binary wire encoding,
negotiated per request). `Fleet.batch` groups tuples by dataset, each
`ReplicaSet.call_batch` groups its tuples by rendezvous-chosen replica and
forwards one `handle_batch` sub-batch RPC per replica over a keep-alive
connection pool; the serving side executes all cold tuples of a sub-batch
as a single cross-dataset super-pack engine call. Per-tuple ETags, 304s,
and failover semantics are identical to the singleton routes — a sub-batch
whose replica dies mid-flight requeues whole onto the next candidate.

Planner tier: the router's `POST /cost` costs a join graph that spans
registered datasets. `Fleet.cost` fetches one routed `/tablestats` body
per referenced dataset (restricted to the join columns the graph uses),
scores the plan space in the router process (`repro.planner`), and mints
a combined ETag over the per-dataset tablestats ETags — 304 exactly when
every input dataset's stats are unchanged, stable across replica
failover because the constituent tags are state-derived. Cost tuples
ride `POST /batch` next to estimate tuples.

Entry points: `repro.launch.serve_fleet` (CLI; `--smoke` is the CI boot
test), `serve_fleet()` (library), `Fleet` + `StatsRouter` for embedding.
"""
from repro.fleet.registry import (  # noqa: F401
    DatasetRegistry,
    DatasetSpec,
    parse_spec,
)
from repro.fleet.replica import (  # noqa: F401
    FAILOVER_ERRORS,
    LocalReplica,
    NoReplicaAvailable,
    RemoteReplica,
    ReplicaError,
    ReplicaSet,
    StatsRequest,
)
from repro.fleet.router import (  # noqa: F401
    Fleet,
    FleetStats,
    StatsRouter,
    default_replica_factory,
    make_router_handler,
    replica_devices,
    serve_fleet,
)
