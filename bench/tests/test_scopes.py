"""The program-scope reduction on small synthetic traces with known answers."""
import pytest

from bench import run
from bench import scopes as sc

GRAD_FWD = "jit(super_round)/while/body/closed_call/hierfavg.local_step.grad/jvp(while)/body/dot_general"
GRAD_BWD = "jit(super_round)/while/body/closed_call/hierfavg.local_step.grad/transpose(jvp(while))/body/dot_general"
OPT = "jit(super_round)/while/body/closed_call/hierfavg.local_step.optimizer/add"
NORM = "jit(super_round)/while/body/closed_call/hierfavg.local_step.grad_norm/reduce_sum"
EDGE = "jit(super_round)/while/body/branch_1_fun/hierfavg.sync.edge/reduce_sum"
CODEC = "jit(super_round)/while/body/branch_1_fun/hierfavg.sync.cloud/hierfavg.codec/round"
PATHS = {"while.1": "jit(super_round)/while", "fusion.2": GRAD_FWD, "fusion.3": GRAD_BWD,
         "fusion.4": OPT, "fusion.5": NORM, "fusion.6": EDGE, "fusion.7": CODEC}


def test_phases_of_paths():
    assert sc.phase_of(GRAD_FWD) == "hierfavg.local_step.grad.forward"
    assert sc.phase_of(GRAD_BWD) == "hierfavg.local_step.grad.backward"
    assert sc.phase_of(CODEC) == "hierfavg.codec"  # the innermost scope
    assert sc.phase_of("jit(super_round)/while/body/dynamic_update_slice") is None
    assert sc.phase_of("src/repro/core/hierfavg.py:1046") is None  # a source file, not a scope
    assert sc.phase_of(OPT + ":") == "hierfavg.local_step.optimizer"  # as TPU traces write it
    assert sc.phase_of(None) is None


def test_scoped_ops_nested_in_a_while():
    # a while loop over 0-10 s holds the step's ops; its own 1.5 s (less its
    # children) is outside every scope
    device = {0: [("while.1", 0.0, 10.0), ("fusion.2", 0.5, 3.5), ("fusion.3", 3.5, 7.5),
                  ("fusion.4", 7.5, 9.0), ("fusion.5", 9.0, 9.5)]}
    red = sc.reduce_scopes(device, PATHS, [], (0.0, 10.0))
    assert red["scoped"] and not red["host_spans"]
    assert red["phases"] == pytest.approx({
        "hierfavg.local_step.grad.forward": 3.0, "hierfavg.local_step.grad.backward": 4.0,
        "hierfavg.local_step.optimizer": 1.5, "hierfavg.local_step.grad_norm": 0.5,
    })
    assert red["unscoped_s"] == pytest.approx(1.0)
    assert red["busy_s"] == pytest.approx(10.0)
    assert red["idle"] == {}


def test_syncs_and_the_codec_inside_them_averaged_over_chips():
    device = {0: [("fusion.6", 0.0, 2.0), ("fusion.7", 2.0, 3.0)],
              1: [("fusion.6", 0.0, 4.0), ("fusion.7", 4.0, 5.0)]}
    red = sc.reduce_scopes(device, PATHS, [], (0.0, 10.0))
    assert red["phases"] == pytest.approx({"hierfavg.sync.edge": 3.0, "hierfavg.codec": 1.0})
    assert red["chips"] == 2 and red["busy_s"] == pytest.approx(4.0)


def test_idle_under_the_prefetch_wait_the_engine_and_the_harness():
    # ops 0-4 and 5-8 of a 10 s window; the engine thread is in interval 0
    # until 9.8 s, waits for its block over 3.5-4.6 and flushes over 8.5-9.5
    device = {0: [("fusion.4", 0.0, 4.0), ("fusion.4", 5.0, 8.0), ("fusion.4", 8.00001, 8.00002)]}
    engine = [("fed.run", 0.0, 9.8), ("fed.interval", 0.0, 9.8), ("fed.prefetch_wait", 3.5, 4.6),
              ("fed.dispatch", 4.6, 4.7), ("fed.flush", 8.5, 9.5)]
    red = sc.reduce_scopes(device, PATHS, engine, (0.0, 10.0))
    # 4-4.6 waits for the block; 4.6-5, 8-8.5 (less a 10 us op and the gap
    # before it, too short to count), 8.5-9.5 and 9.5-9.8 are the engine's;
    # 9.8-10 lies in no program span
    assert red["idle"] == pytest.approx({"data_wait": 0.6, "engine": 0.4 + 0.49998 + 1.0 + 0.3, "harness": 0.2})


def test_data_spans_on_the_engine_thread_count_as_waiting_for_data():
    device = {0: [("fusion.4", 0.0, 1.0), ("fusion.4", 2.0, 3.0)]}
    engine = [("fed.interval", 0.0, 3.0), ("fed.prefetch_wait", 0.9, 2.0), ("data.block_gather", 1.0, 1.5)]
    red = sc.reduce_scopes(device, PATHS, engine, (0.0, 3.0))
    assert red["idle"] == pytest.approx({"data_wait": 1.0})


def _metric(name, red, monkeypatch, intervals=4):
    monkeypatch.setattr(sc, "window_of", lambda ctx: red)
    mod = run.load_module(run.BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")
    return mod.read({"intervals": intervals, "chips": 1})


def test_metrics_are_milliseconds_per_interval(monkeypatch):
    device = {0: [("while.1", 0.0, 10.0), ("fusion.2", 0.5, 3.5), ("fusion.3", 3.5, 7.5),
                  ("fusion.4", 7.5, 9.0), ("fusion.5", 9.0, 9.5), ("fusion.6", 11.0, 12.0),
                  ("fusion.7", 12.0, 12.5)]}
    engine = [("fed.interval", 0.0, 20.0), ("fed.prefetch_wait", 12.5, 13.0), ("fed.flush", 13.0, 20.0)]
    red = sc.reduce_scopes(device, PATHS, engine, (0.0, 20.0))
    want = {"step_forward_ms": 3000 / 4, "step_backward_ms": 4000 / 4, "step_optimizer_ms": 1500 / 4,
            "step_grad_norm_ms": 500 / 4, "edge_sync_ms": 1000 / 4, "cloud_sync_ms": 0.0,
            "data_wait_ms": 500 / 4, "engine_idle_ms": (1000 + 7000) / 4}
    for name, value in want.items():
        assert _metric(name, red, monkeypatch) == pytest.approx(value), name


def test_nothing_to_read_without_scopes_or_spans(monkeypatch):
    device = {0: [("fusion.1", 0.0, 1.0), ("fusion.9", 2.0, 3.0)]}
    unscoped = sc.reduce_scopes(device, {"fusion.1": "jit(f)/add"}, [], (0.0, 3.0))
    assert not unscoped["scoped"] and unscoped["unscoped_s"] == pytest.approx(2.0)
    for name in list(sc.PHASES) + ["data_wait_ms", "engine_idle_ms"]:
        assert _metric(name, unscoped, monkeypatch) is None, name
        assert _metric(name, None, monkeypatch) is None, name  # no trace at all


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def test_op_paths_from_the_event_metadata_of_a_serialized_xspace():
    stat_meta = lambda i, name: (5, _msg((1, i), (2, _msg((1, i), (2, name)))))
    event_meta = lambda i, name, *stats: (4, _msg((1, i), (2, _msg((1, i), (2, name), *[(5, st) for st in stats]))))
    tpu0 = _msg(
        (1, 3), (2, "/device:TPU:0"), (3, _msg((1, 1), (2, "XLA Ops"))),
        stat_meta(7, "tf_op"), stat_meta(8, "long_name"), stat_meta(9, OPT), stat_meta(10, "scope"),
        # the path as a string and as a reference to a stat metadata's
        # name; an op with other stats only, and one with none
        event_meta(1, "%fusion.3 = f32[] transpose(f32[] %x)", _msg((1, 8), (5, "transpose")), _msg((1, 7), (5, GRAD_BWD))),
        event_meta(2, "%fusion.4 = f32[] add(f32[] %y)", _msg((1, 7), (7, 9))),
        event_meta(3, "fusion.5", _msg((1, 10), (5, "src/repro/core/hierfavg.py:1046")), _msg((1, 2), (4, 12))),
        event_meta(4, "%copy.6 = f32[] copy(f32[] %z)"),
    )
    tpu1 = _msg((2, "/device:TPU:1"), event_meta(1, "fusion.9", _msg((1, 7), (5, EDGE))), stat_meta(7, "tf_op"))
    host = _msg((2, "/host:CPU"), event_meta(1, "fusion.8", _msg((1, 7), (5, EDGE))), stat_meta(7, "tf_op"))
    raw = _msg((1, host), (1, tpu0), (1, tpu1), (4, "hostname"))
    assert sc.op_paths(raw, [0]) == {"fusion.3": GRAD_BWD, "fusion.4": OPT, "fusion.5": "", "copy.6": ""}
    assert sc.op_paths(raw, [0, 1])["fusion.9"] == EDGE


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        sc.reduce_scopes({}, {}, [], (0.0, 1.0))
