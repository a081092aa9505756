"""Benchmark harness — one module per paper table/claim.

Prints ``name,us_per_call,derived`` CSV (one row per measurement):

  accuracy.py     — Table 1 regime grid + coverage/rowgroup/length sweeps
  baselines.py    — zero-cost vs data-access estimators (§11 positioning)
  batch_memory.py — §8 batch dictionary prediction vs measured
  catalog_scale.py— StatsCatalog cold/warm/incremental latency + retraces
  complexity.py   — §10.2 single-pass complexity table
  engine_scale.py — EstimationEngine local/sharded/chunked/composed throughput
  fleet_latency.py — routed vs direct overhead, failover, shared-spill warmth
  kernels.py      — Pallas kernel suite throughput
  obs_overhead.py — telemetry tier on-vs-off warm latency + ETag parity
  planner.py      — batched plan-scoring throughput + warm /cost 304 latency
  service_latency.py — stats-service cold/warm/304 latency + throughput
  warehouse.py    — TPC-H-shaped lineitem accuracy via the catalog (§10.1)

``--quick`` runs every module at tiny shapes (CI smoke: exercises the
harness end to end in seconds; the numbers mean nothing).

``--json PATH`` additionally writes the rows as a machine-readable
artifact — the CI quick-benchmark step uploads it per run, so the repo
accumulates a perf trajectory across PRs instead of one-off terminal
output. The schema is deliberately flat: ``{"quick": bool, "git_sha":
str, "generated_at": iso8601, "rows": [{"name", "us_per_call",
"derived"}, ...], "errors": [module, ...]}``. `git_sha`/`generated_at`
pin each artifact to the exact tree and wall-clock it measured, so two
BENCH files can be diffed meaningfully (`benchmarks/compare.py`).
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import traceback


def git_sha() -> str:
    """HEAD commit of the tree being measured; "unknown" outside a repo."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def build_payload(rows, errors) -> dict:
    """The BENCH artifact schema (see module docstring)."""
    return {
        "quick": bool(os.environ.get("NDV_BENCH_QUICK")),
        "git_sha": git_sha(),
        "generated_at": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "rows": rows,
        "errors": errors,
    }


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--quick" in argv:
        argv.remove("--quick")
        # Before importing any benchmark module: they read the flag at
        # module/call scope through benchmarks._quick.
        os.environ["NDV_BENCH_QUICK"] = "1"
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json requires a PATH argument")
        del argv[i : i + 2]
    if argv:
        raise SystemExit(f"unknown arguments: {argv}")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        accuracy,
        baselines,
        batch_memory,
        catalog_scale,
        complexity,
        engine_scale,
        fleet_latency,
        kernels,
        obs_overhead,
        planner,
        service_latency,
        warehouse,
    )

    modules = [
        ("accuracy", accuracy),
        ("warehouse", warehouse),
        ("catalog_scale", catalog_scale),
        ("engine_scale", engine_scale),
        ("service_latency", service_latency),
        ("fleet_latency", fleet_latency),
        ("obs_overhead", obs_overhead),
        ("planner", planner),
        ("baselines", baselines),
        ("batch_memory", batch_memory),
        ("complexity", complexity),
        ("kernels", kernels),
    ]
    print("name,us_per_call,derived")
    rows = []
    errors = []
    for name, mod in modules:
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}")
                rows.append({
                    "name": row_name,
                    "us_per_call": round(us, 1),
                    "derived": derived,
                })
        except Exception as e:  # pragma: no cover
            errors.append(name)
            traceback.print_exc()
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
    if json_path:
        payload = build_payload(rows, errors)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {len(rows)} rows to {json_path}", file=sys.stderr)
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    main()
