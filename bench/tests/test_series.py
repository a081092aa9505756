"""What a window added to the program's metrics registry, for the readers."""
import run


def test_series_delta_covers_counters_and_histograms():
    before = {("c", (("tier", "router"),)): 2.0,
              ("h", (("tier", "router"),)): (0.5, 4)}
    after = {("c", (("tier", "router"),)): 5.0,
             ("c", (("tier", "replica"),)): 1.0,
             ("h", (("tier", "router"),)): (1.25, 7)}
    d = run.series_delta(before, after)
    assert sorted((l["tier"], v) for l, v in d["c"]) == [("replica", 1.0),
                                                          ("router", 3.0)]
    assert d["h"] == [({"tier": "router"}, {"sum": 0.75, "count": 3})]
    assert run.series_total(d, "c") == 4.0
    assert run.series_total(d, "missing") == 0


def test_registry_values_read_the_program_registry():
    from repro.obs import registry

    registry().counter("bench_test_total", "a test counter").inc(3, k="v")
    values = run.registry_values()
    assert values[("bench_test_total", (("k", "v"),))] == 3.0
