"""The trace reduction on small synthetic traces with known answers."""
import pytest

from bench import trace as tr


def test_union_subtract_measure():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert tr.measure(u) == 5
    assert tr.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert tr.subtract([(0, 3), (4, 8)], [(1, 2), (5, 9)]) == [(0, 1), (2, 3), (4, 5)]


def test_busy_idle_and_gap_names():
    # one chip; window 0..10 s; ops cover 0-4 and 6-9 (busy 7 s); host
    # spans: a chunk over 0-5 and a second one over 5-10
    device = {0: [("fusion.1", 0.0, 3.0), ("dot.2", 2.0, 4.0), ("fusion.1", 6.0, 9.0)]}
    host = [("python", "bench.chunk", 0.0, 5.0), ("python", "bench.chunk", 5.0, 10.0),
            ("prefetch", "bench.batch_gather", 4.0, 6.0), ("python", "bench.window", 0.0, 10.0)]
    red = tr.reduce_events(device, host, (0.0, 10.0))
    assert red["busy_s"] == pytest.approx(7.0)
    assert red["window_s"] == pytest.approx(10.0)
    # ranked by self time: dot.2 runs inside fusion.1's span from 2 to 3
    assert dict((n, t) for n, t in red["device_ops"]) == pytest.approx({"fusion.1": 5.0, "dot.2": 2.0})
    gaps = dict((n, t) for n, t in red["idle_gaps"])
    # gap 4-6 overlaps the gather span fully (2 s) and each chunk by 1 s;
    # gap 9-10 lies in the second chunk only
    assert gaps == pytest.approx({"prefetch:bench.batch_gather": 2.0, "python:bench.chunk": 1.0})
    assert red["collective_ops"] == 0 and red["collective_exposed_s"] == 0


def test_short_gaps_are_lumped_and_window_clips():
    device = {0: [("a", -1.0, 1.0), ("b", 1.00001, 2.0), ("c", 2.5, 12.0)]}
    red = tr.reduce_events(device, [], (0.0, 10.0))
    gaps = dict((n, t) for n, t in red["idle_gaps"])
    assert gaps[tr.SHORT_GAP] == pytest.approx(1e-5)
    assert gaps["host:no_bench_span"] == pytest.approx(0.5)
    assert red["busy_s"] == pytest.approx(10.0 - 0.5 - 1e-5)


def test_exposed_collectives_averaged_over_chips():
    # chip 0: an async all-reduce in flight 5-8, compute until 6 -> 2 s
    # exposed; chip 1: the same all-reduce with nothing beside it -> 3 s
    device = {0: [("fusion", 0.0, 6.0)], 1: [("fusion", 0.0, 5.0)]}
    in_flight = {0: [("all-reduce-start.1", 5.0, 8.0)], 1: [("all-reduce-start.1", 5.0, 8.0)]}
    red = tr.reduce_events(device, [], (0.0, 10.0), async_collectives=in_flight)
    assert red["collective_ops"] == 2
    assert red["collective_exposed_s"] == pytest.approx(2.5)
    assert red["busy_s"] == pytest.approx(8.0)
    assert red["chips"] == 2


def test_loops_that_enclose_a_collective_do_not_hide_it():
    # a while loop spans its body: two fusions and a synchronous all-reduce
    device = {0: [("while.1", 0.0, 10.0), ("fusion.2", 0.5, 4.0), ("all-reduce.3", 4.0, 6.0),
                  ("fusion.4", 6.0, 9.5)]}
    red = tr.reduce_events(device, [], (0.0, 10.0))
    assert red["collective_exposed_s"] == pytest.approx(2.0)
    ops = dict((n, t) for n, t in red["device_ops"])
    assert ops == pytest.approx({"fusion.4": 3.5, "fusion.2": 3.5, "all-reduce.3": 2.0, "while.1": 1.0})
    assert red["busy_s"] == pytest.approx(10.0)


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({}, [], (0.0, 1.0))


def test_op_names_drop_the_instruction_text():
    text = "%all-reduce.5 = f32[4,768]{1,0} all-reduce(f32[4,768]{1,0} %fusion.418), replica_groups={}"
    assert tr.op_name(text) == "all-reduce.5"
    assert tr.op_name("%fusion.2 = f32[] fusion(f32[] %all-reduce.3)") == "fusion.2"
    assert not tr.COLLECTIVE.search(tr.op_name("%fusion.2 = f32[] fusion(f32[] %all-reduce.3)"))
