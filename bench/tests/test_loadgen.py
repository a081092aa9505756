"""The open-loop schedule: fixed by the traffic, whatever the run's seed."""
import collections

import pytest

import loadgen


def _schedule(**traffic):
    graphs = [f"q{i:02d}" for i in range(10)]
    plan = {"router": "http://127.0.0.1:1", "seconds": 20.0,
            "schedule_seed": 7, "clients": 4, "probe_rate": 50.0,
            "graphs": {g: {"body": {}} for g in graphs},
            "probe_graphs": graphs, **traffic}
    return loadgen.Generator(plan)._open_schedule()


@pytest.mark.parametrize("traffic", [
    {}, {"templates": {"dist": "uniform"}},
    {"templates": {"dist": "zipf", "exponent": 1.2},
     "bursts": {"on_s": 2.0, "off_s": 3.0}}], ids=["default", "uniform", "zipf_bursts"])
def test_schedule_is_fixed_and_within_the_window(traffic):
    a, b = _schedule(**traffic), _schedule(**traffic)
    assert a == b
    assert len(a) == 1000
    times = [t for t, _, _ in a]
    assert times == sorted(times) and 0 <= times[0] and times[-1] < 20.0


def test_uniform_sends_each_template_equally_often():
    counts = collections.Counter(g for _, _, g in _schedule())
    assert set(counts.values()) == {100}


def test_zipf_counts_fall_with_rank():
    counts = sorted(collections.Counter(
        g for _, _, g in _schedule(templates={"dist": "zipf",
                                              "exponent": 1.2})).values(),
        reverse=True)
    assert sum(counts) == 1000
    assert counts[0] > 3 * counts[-1]


def test_bursts_leave_the_off_periods_empty():
    sched = _schedule(bursts={"on_s": 2.0, "off_s": 3.0})
    assert all(t % 5.0 < 2.0 for t, _, _ in sched)
    # The window holds four whole cycles: the on-time is 4 x 2 s.
    assert max(t for t, _, _ in sched) > 15.0


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        _schedule(templates={"dist": "pareto"})
