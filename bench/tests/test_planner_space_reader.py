"""The reader of the planner's plan-space cache counter."""
import run


def _read(series):
    return run.load_readers(["planner_space_hit_share.maint"])[
        "planner_space_hit_share.maint"]({"series": series})


def test_a_program_without_the_cache_reads_nothing():
    assert _read({"planner_dispatches_total": [({}, 12.0)]}) is None
    assert _read({}) is None
    assert _read({"planner_space_cache_total": [
        ({"result": "hit"}, 0.0), ({"result": "miss"}, 0.0)]}) is None


def test_hits_over_lookups_in_the_window():
    from repro.planner import (
        ColumnStats, TableStats, compute_cost, parse_join_graph)

    graph = parse_join_graph({
        "tables": [{"name": f"t{i}"} for i in range(4)],
        "edges": [{"left": f"t{i}", "left_column": "k",
                   "right": f"t{i + 1}", "right_column": "k"}
                  for i in range(3)]})
    stats = {f"t{i}": TableStats(rows=100.0, columns={
        "k": ColumnStats(ndv=10.0, non_null=100)}) for i in range(4)}
    # A size of its own, so the first lookup builds it.
    before = run.registry_values()
    for _ in range(4):
        compute_cost(graph, stats, mode="paper", max_plans=23)
    series = run.series_delta(before, run.registry_values())
    assert _read(series) == 75.0
