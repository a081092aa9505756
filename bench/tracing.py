"""Reduction of a profiler trace to the numbers the per-layer metrics read.

`reduce(trace_dir)` reads the ``.xplane.pb`` that `jax.profiler` wrote and
returns, for the device planes (``/device:TPU:N``):

  busy_s        seconds in which some operation ran ("XLA Ops" intervals,
                merged), averaged over the devices that ran anything;
  ops           seconds per operation name (the HLO name before " = ");
  modules       seconds and count per program ("XLA Modules", e.g. jit_fold);
  kernels       per custom-call kernel name: seconds, count, and the bytes
                its operands and result occupy, read from the HLO text of
                each event (`hlo_bytes`);
  host_spans    seconds and count per name of every event on the host
                planes (the harness's annotations, and any
                `jax.profiler.TraceAnnotation` the program adds);
  gaps          the ten longest idle intervals between device operations,
                each labelled with the innermost of the benchmark's host
                annotations (`ANNOTATIONS`) that covers half of it.

Event times of every plane share the profiler's clock, so a device gap and a
host annotation can be compared directly.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}

# Host spans the harness adds around the program's layers (trace runs
# only), innermost first: a gap is labelled with the innermost span that
# covers at least half of it.
ANNOTATIONS = ("engine.estimate", "catalog.pack", "ingest.refresh",
               "service.tablestats", "planner.compute_cost", "router.cost",
               "http.request")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _ITEMSIZE[dtype]
    return total


_OPCODE = re.compile(r"\s[a-z][a-z0-9\-]*\(")


def hlo_bytes(text: str) -> int:
    """Bytes of the result and of every operand of one HLO instruction
    (``%x = <result shape> opcode(<operands>), attributes``): what the
    instruction has to write and read at least once. Shapes among the
    attributes (layout constraints) are not counted."""
    rest = text.partition(" = ")[2]
    m = _OPCODE.search(rest)
    if m is None:
        return _shape_bytes(rest)
    depth, i = 1, m.end()
    while i < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    return _shape_bytes(rest[:m.start()]) + _shape_bytes(rest[m.end():i - 1])


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce(trace_dir: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(trace_file(trace_dir))
    busy_per_device = []
    ops: Dict[str, float] = collections.Counter()
    modules: Dict[str, list] = {}
    kernels: Dict[str, list] = {}
    spans: List[Tuple[int, int, str]] = []
    host_spans: Dict[str, list] = {}
    busy_all: List[Tuple[int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            intervals = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        a, d = int(e.start_ns), int(e.duration_ns)
                        intervals.append((a, a + d))
                        name = e.name.partition(" = ")[0]
                        ops[name] += d * 1e-9
                        if " custom-call(" in e.name:
                            k = kernels.setdefault(
                                name.lstrip("%").split(".")[0], [0.0, 0, 0])
                            k[0] += d * 1e-9
                            k[1] += 1
                            k[2] += hlo_bytes(e.name)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        m = modules.setdefault(e.name.split("(")[0],
                                               [0.0, 0])
                        m[0] += e.duration_ns * 1e-9
                        m[1] += 1
            merged = _merge(intervals)
            if merged:
                busy_per_device.append(sum(b - a for a, b in merged) * 1e-9)
                busy_all += merged
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    h = host_spans.setdefault(e.name, [0.0, 0])
                    h[0] += e.duration_ns * 1e-9
                    h[1] += 1
                    if e.name in ANNOTATIONS:
                        a = int(e.start_ns)
                        spans.append((a, a + int(e.duration_ns), e.name))
    busy = _merge(busy_all)
    idle = sorted(((a, b) for (_, a), (b, _) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:10]
    gaps = []
    for a, b in idle:
        cover: Dict[str, int] = collections.Counter()
        for s0, s1, name in spans:
            lo, hi = max(a, s0), min(b, s1)
            if hi > lo:
                cover[name] += hi - lo
        label = next((n for n in ANNOTATIONS if 2 * cover.get(n, 0) >= b - a),
                     "host, unannotated")
        gaps.append((label, (b - a) * 1e-9))
    return {
        "busy_s": (sum(busy_per_device) / len(busy_per_device)
                   if busy_per_device else 0.0),
        "ops": dict(ops),
        "modules": {k: {"seconds": v[0], "count": v[1]}
                    for k, v in modules.items()},
        "kernels": {k: {"seconds": v[0], "count": v[1], "bytes": v[2]}
                    for k, v in kernels.items()},
        "host_spans": {k: {"seconds": v[0], "count": v[1]}
                       for k, v in host_spans.items()},
        "gaps": gaps,
    }


def breakdown(reduced: dict) -> dict:
    """The ten costliest device operations and the ten longest idle gaps."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(reduced["gaps"], key=lambda g: -g[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
