"""Whether what the window served is correct, against the plain reference.

Three layers, one number or count each, every one printed beside its limit:

  stale        ingest and catalog: an answer sent after a writer's refresh
               was acknowledged that reflects an older state of that table;
               a refresh acknowledged with the wrong generation; a served
               file set, or row count, that differs from the committed
               snapshot. Limit 0.
  lost         requests that were never answered. Limit 0.
  ndv_gap      engine and kernel: the largest relative gap between a served
               column NDV (/tablestats) and the reference's paper-mode NDV of
               the same files. Checked: every table's state before the
               window, and a sample of the writer's post-commit reads drawn
               from the seed.
  card_gap     planner: the largest relative gap between a served /cost
               cardinality (or C_out) and the reference's float32 fold of the
               served order over the reference's NDVs and row counts, on
               every /cost body the window served.
  plan_excess  planner: how much dearer the served order is than the
               reference's cheapest, by the reference's costs, on every
               body whose graph has no more orders than the request's plan
               budget (`max_plans`, the program's 4,096 by default): there
               /cost searches the whole plan space; past it, a fixed sample.

The limits of the last three are the configuration's (``limits``); each is
set between the largest reading of sound runs and the smallest reading of
the control: the reference computed in bfloat16 in the program's place.
"""
from __future__ import annotations

import bisect
import json
import math
from typing import Dict, List, Optional

import numpy as np

import reference

WRITES_CHECKED = 24   # post-commit /tablestats reads compared per run
# The plan budget of a /cost request that names none: the program enumerates
# every left-deep order up to this many, and samples past it.
DEFAULT_MAX_PLANS = 4096


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


class Reference:
    """Reference answers per (table, generation), computed once each."""

    def __init__(self, world, sources, file_data, dt=np.float64):
        self.world, self.sources, self.file_data = world, sources, file_data
        self.dt = dt
        self._ndv: Dict[tuple, float] = {}

    def live(self, table: str, gen: int):
        return self.sources[table].history[gen - 1]

    def rows(self, table: str, gen: int) -> int:
        files = self.file_data[table]
        return sum(files[f].rows for f in self.live(table, gen))

    def ndv(self, table: str, gen: int, column: str) -> float:
        key = (table, gen, column)
        if key not in self._ndv:
            col = next(c for c in self.world.tables[table].columns
                       if c.name == column)
            files = self.file_data[table]
            self._ndv[key] = reference.paper_ndv(
                col.kind, col.width,
                [files[f].columns[column] for f in self.live(table, gen)],
                self.dt)
        return self._ndv[key]


def _planner_inputs(ref: Reference, template: dict, gens: Dict[str, int],
                    ns: str, dt):
    """(base rows, edges) of a template at the given table generations."""
    index = {a: i for i, (a, _, _) in enumerate(template["tables"])}
    table_of = {a: t for a, t, _ in template["tables"]}
    base = [dt(dt(ref.rows(t, gens[f"{ns}/{t}"])) * dt(s))
            for _, t, s in template["tables"]]
    edges = []
    for l, lc, r, rc in template["edges"]:
        nl = max(1.0, ref.ndv(table_of[l], gens[f"{ns}/{table_of[l]}"], lc))
        nr = max(1.0, ref.ndv(table_of[r], gens[f"{ns}/{table_of[r]}"], rc))
        edges.append((index[l], index[r], max(nl, nr)))
    return base, edges, index


def _plan_gaps(template, body, ref, gens, ns, whole_space):
    """(card_gap, plan_excess) of one served /cost body; plan_excess is None
    where the plan space is not searched whole."""
    base, edges, index = _planner_inputs(ref, template, gens, ns, np.float32)
    order = [index[a] for a in body["best_order"]]
    cost, cards = reference.fold_order(order, base, edges, np.float32)
    gap = 0.0
    if len(order) > 1:
        gap = reference.rel_gap(float(body["total_cost"]), cost)
        for j, want in zip(body["joins"], cards):
            gap = max(gap, reference.rel_gap(float(j["cardinality"]), want))
    if not whole_space:
        return gap, None
    plans, costs, _ = reference.plan_costs(base, edges, np.float32)
    best = reference.best_plan(plans, costs)
    excess = 0.0
    if len(order) > 1 and math.isfinite(costs[best]) and costs[best] > 0:
        excess = float((cost - costs[best]) / costs[best]) \
            if math.isfinite(cost) else math.inf
    return gap, max(excess, 0.0)


def _control_plan(template, body, ref, ctrl, gens, ns, whole_space):
    """The control's (card_gap, plan_excess): plans costed in bfloat16. Where
    the plan space is searched whole the control picks its own cheapest
    order; past the budget it serves the program's order, folded in
    bfloat16."""
    bf = _bf16()
    cbase, cedges, index = _planner_inputs(ctrl, template, gens, ns, bf)
    if whole_space:
        plans, costs, cards = reference.plan_costs(cbase, cedges, bf)
        pick = reference.best_plan(plans, costs)
        order = [int(x) for x in plans[pick]]
        total, step_cards = costs[pick], cards[pick]
    else:
        order = [index[a] for a in body["best_order"]]
        total, step_cards = reference.fold_order(order, cbase, cedges, bf)
    served = {"best_order": [template["tables"][i][0] for i in order],
              "total_cost": total,
              "joins": [{"cardinality": c} for c in step_cards]}
    return _plan_gaps(template, served, ref, gens, ns, whole_space)


def check_window(*, config, world, sources, file_data, ns, seed, ready,
                 result, served, tablestats_etags, templates,
                 max_plans=None, control=False) -> dict:
    limits = config["limits"]
    rng = np.random.default_rng([int(seed) % (1 << 63), 11])
    ref = Reference(world, sources, file_data)
    stale = lost = 0

    # -- ingest and catalog: commits acknowledged, and in the served state ---
    acked: Dict[str, List[tuple]] = {}
    for w in result["writes"]:
        if "error" in w or not isinstance(w.get("refresh"), dict):
            lost += 1
            continue
        expect = w["commit"]["generation"]
        answers = w["refresh"]["refreshed"].get(w["dataset"], {})
        if not answers or any(
                not isinstance(a, dict) or a["generation"] != expect
                or not a["changed"] for a in answers.values()):
            stale += 1
        stale += int(w["stale"])
        if "t_fresh" not in w and not w["stale"]:
            lost += 1
        acked.setdefault(w["dataset"], []).append((w["t_ack"], expect))
    for table, replicas in served.items():
        for files, gen in replicas:
            if tuple(files) != sources[table].history[-1] or \
                    gen != len(sources[table].history):
                stale += 1

    # -- answers sent after an acknowledged commit reflect it -----------------
    bodies = {tag: (gid, json.loads(text))
              for tag, (gid, text) in result["cost_bodies"].items()}

    def generations(tag) -> Optional[Dict[str, int]]:
        if tag not in bodies:
            return None
        out = {}
        for key, ts_tag in bodies[tag][1]["sources"].items():
            if ts_tag not in tablestats_etags:
                return None
            out[key] = tablestats_etags[ts_tag][1]
        return out

    for key in acked:
        acked[key].sort()
    for r in result["records"]:
        status, tag, t_send = r[3], r[7], r[1]
        if status not in (200, 304):
            continue
        gens = generations(tag)
        if gens is None:
            stale += 1
            continue
        for key, gen in gens.items():
            acks = acked.get(key, [])
            i = bisect.bisect_left([a[0] for a in acks], t_send)
            if i and gens[key] < acks[i - 1][1]:
                stale += 1
                break
    lost += max(result.get("scheduled", len(result["records"]))
                - len(result["records"]), 0)
    lost += int(result.get("lost_threads", 0))

    # -- engine and kernel: served NDVs against the reference -----------------
    states = [(key, body) for key, body in ready["tablestats"].items()
              if isinstance(body, dict)]
    if isinstance(ready["tablestats"], dict):
        lost += sum(1 for b in ready["tablestats"].values()
                    if not isinstance(b, dict))
    reads = [(w["dataset"], w["tablestats"]) for w in result["writes"]
             if isinstance(w.get("tablestats"), dict)]
    if len(reads) > WRITES_CHECKED:
        pick = rng.choice(len(reads), WRITES_CHECKED, replace=False)
        reads = [reads[i] for i in sorted(pick)]
    states += reads
    ndv_gap = 0.0
    for key, body in states:
        table = key.split("/", 1)[1]
        gen = int(body["generation"])
        if int(body["rows"]) != ref.rows(table, gen):
            stale += 1
        for column, stats in body["columns"].items():
            ndv_gap = max(ndv_gap, reference.rel_gap(
                float(stats["ndv"]), ref.ndv(table, gen, column)))

    # -- planner: served orders and cardinalities against the reference -------
    budget = int(max_plans or DEFAULT_MAX_PLANS)
    card_gap = plan_excess = 0.0
    planned = []
    for tag in sorted(bodies):
        gid, body = bodies[tag]
        gens = generations(tag)
        if gens is None:
            continue
        template = templates[gid]
        for t in body["tables"]:
            if int(t["rows"]) != ref.rows(t["dataset"], gens[
                    f"{ns}/{t['dataset']}"]):
                stale += 1
        whole = math.factorial(len(template["tables"])) <= budget
        gap, excess = _plan_gaps(template, body, ref, gens, ns, whole)
        card_gap = max(card_gap, gap)
        if excess is not None:
            plan_excess = max(plan_excess, excess)
        planned.append((template, body, gens, whole))

    readings = {"stale": (stale, 0), "lost": (lost, 0),
                "ndv_gap": (ndv_gap, limits["ndv_gap"]),
                "card_gap": (card_gap, limits["card_gap"]),
                "plan_excess": (plan_excess, limits["plan_excess"])}
    out = {
        "correct": all(v <= lim for v, lim in readings.values()),
        "checks": {k: {"value": v, "limit": lim}
                   for k, (v, lim) in readings.items()},
        "states_checked": len(states), "plans_checked": len(planned),
        "excess_checked": sum(1 for p in planned if p[3]),
    }
    if control:
        ctrl = Reference(world, sources, file_data, _bf16())
        c_ndv = 0.0
        for key, body in states:
            table = key.split("/", 1)[1]
            gen = int(body["generation"])
            for column in body["columns"]:
                c_ndv = max(c_ndv, reference.rel_gap(
                    ctrl.ndv(table, gen, column), ref.ndv(table, gen, column)))
        c_card = c_excess = 0.0
        for template, body, gens, whole in planned:
            gap, excess = _control_plan(template, body, ref, ctrl, gens, ns,
                                        whole)
            c_card = max(c_card, gap)
            if excess is not None:
                c_excess = max(c_excess, excess)
        out["control"] = {"ndv_gap": c_ndv, "card_gap": c_card,
                          "plan_excess": c_excess,
                          "fails": c_ndv > limits["ndv_gap"]
                          or c_card > limits["card_gap"]
                          or c_excess > limits["plan_excess"]}
    return out
