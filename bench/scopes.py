"""Per-layer times from the program's own names in a traced window.

The program names its phases twice (``docs/performance.md``, "Profiling a
run"): device ops carry ``jax.named_scope`` paths in their metadata
(``hierfavg.local_step.grad``, ``hierfavg.sync.edge``, ...), which a TPU
trace keeps per op in the ``tf_op`` stat; the engine and its prefetch
worker put ``jax.profiler.TraceAnnotation`` spans on the host's threads
(``fed.*`` and ``data.*``). Inside the harness's ``bench.window`` span,
per chip of the cell:

* phases: each op's self time (less the ops nested in it, as
  ``bench/trace.py`` ranks them) goes to the innermost ``hierfavg.*``
  scope on its path; the gradient's scope splits into its forward and its
  backward part by ``transpose(`` on the path (what JAX names the backward
  pass);
* idle: the window less the union of the ops (and collectives in flight),
  gaps shorter than ``SHORT_GAP_S`` left out (they lie between the ops of
  one program), each instant named by the engine thread's innermost
  ``fed.*`` or ``data.*`` span: waiting for a block (``fed.prefetch_wait``,
  or the block's own ``data.*`` work when there is no worker thread), in
  another ``fed.*`` span (the engine), or in none (the harness).

Seconds are averaged over chips. The trace is read once per window and the
result kept with the window's metric context, so each further metric that
reads it costs nothing.

    python3 -m bench.scopes [trace dir] [chips]    # the whole reduction, as JSON
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import (
    ASYNC_LINE,
    COLLECTIVE,
    DEVICE_PLANE,
    OP_LINE,
    SHORT_GAP_S,
    WINDOW_SPAN,
    clip3,
    find_xplane,
    nest,
    op_name,
    subtract,
    union,
)

# the program's scopes (not, say, the ``hierfavg.py`` of a source stat)
SCOPE = re.compile(r"hierfavg\.(?:local_step|sync|codec)[A-Za-z0-9_.]*")
GRAD = "hierfavg.local_step.grad"
BACKWARD = "transpose("
PATH_STAT = "tf_op"  # an op's metadata path on TPU xplanes
HOST_SPAN = re.compile(r"^(fed|data)\.")
WAIT = "fed.prefetch_wait"

# metric name -> the phase it reads
PHASES = {
    "step_forward_ms": GRAD + ".forward",
    "step_backward_ms": GRAD + ".backward",
    "step_optimizer_ms": "hierfavg.local_step.optimizer",
    "step_grad_norm_ms": "hierfavg.local_step.grad_norm",
    "edge_sync_ms": "hierfavg.sync.edge",
    "cloud_sync_ms": "hierfavg.sync.cloud",
}

Op = Tuple[str, float, float]


def phase_of(path: Optional[str]) -> Optional[str]:
    """The phase an op's metadata path names: its innermost ``hierfavg.*``
    scope, the gradient's split into forward and backward; None outside
    every scope."""
    found = SCOPE.findall(path or "")
    if not found:
        return None
    inner = found[-1]
    if inner == GRAD:
        return inner + (".backward" if BACKWARD in path else ".forward")
    return inner


def _innermost(spans: Sequence[Tuple[str, float, float]], lo: float, hi: float):
    """Split [lo, hi) by the spans of one thread (which nest); yields
    (innermost span name or None, start, end)."""
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in spans if e > lo and s < hi]
    cuts = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)})
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        covering = [(s, -(e - s), n) for n, s, e in inside if s <= mid < e]
        yield (max(covering)[2] if covering else None), a, b


def reduce_scopes(
    device: Dict[int, List[Op]],
    paths: Dict[str, str],
    engine: List[Tuple[str, float, float]],
    window: Tuple[float, float],
    *,
    in_flight: Dict[int, List[Op]] = None,
) -> dict:
    """Phase and idle seconds of one window, averaged over chips.

    ``device``: chip -> [(op name, start, end)], nested as executed;
    ``paths``: op name -> its metadata path; ``engine``: the engine
    thread's ``fed.*``/``data.*`` spans [(name, start, end)];
    ``in_flight``: chip -> collectives in flight beside the ops."""
    lo, hi = window
    chips = sorted(device)
    if not chips:
        raise ValueError("the trace holds no device ops")
    in_flight = in_flight or {}
    phases: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy = unscoped = 0.0
    scoped = False
    for chip in chips:
        ops = nest(clip3(device[chip], lo, hi))
        for name, _, _, self_s, _ in ops:
            phase = phase_of(paths.get(name))
            if phase is None:
                unscoped += self_s
            else:
                scoped = True
                phases[phase] += self_s
        covered = union([(s, e) for _, s, e, _, _ in ops] + [(s, e) for _, s, e in clip3(in_flight.get(chip, []), lo, hi)])
        busy += sum(e - s for s, e in covered)
        for g0, g1 in subtract([(lo, hi)], covered):
            if g1 - g0 < SHORT_GAP_S:
                continue
            for span, a, b in _innermost(engine, g0, g1):
                if span is None:
                    kind = "harness"
                elif span == WAIT or span.startswith("data."):
                    kind = "data_wait"
                else:
                    kind = "engine"
                idle[kind] += b - a
    n = len(chips)
    return {
        "window_s": hi - lo,
        "chips": n,
        "busy_s": busy / n,
        "scoped": scoped,
        "phases": {k: v / n for k, v in sorted(phases.items())},
        "unscoped_s": unscoped / n,
        "host_spans": bool(engine),
        "idle": {k: v / n for k, v in sorted(idle.items())},
    }


def read_xplane(path: str, chips: Sequence[int]):
    """(device ops by chip, op paths, engine spans, window, in-flight
    collectives by chip) from one xplane file. The engine thread is the
    host line that holds ``fed.*`` spans."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    device: Dict[int, List[Op]] = {}
    in_flight: Dict[int, List[Op]] = {}
    engine: list = []
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in chips:
            chip = int(m.group(1))
            ops = device.setdefault(chip, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns * 1e-9, ev.end_ns * 1e-9) for ev in line.events)
                elif line.name == ASYNC_LINE:
                    in_flight.setdefault(chip, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        for ev in line.events if COLLECTIVE.search(op_name(ev.name))
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    elif HOST_SPAN.match(ev.name):
                        spans.append((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                if any(n.startswith("fed.") for n, _, _ in spans):
                    engine.extend(spans)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return device, op_paths(raw, chips), engine, window, in_flight


# The per-op stats live in each plane's event metadata, which
# ``ProfileData`` does not expose; they are read from the serialized
# ``XSpace`` (tsl/profiler/protobuf/xplane.proto) by field number:
# XSpace.planes 1; XPlane.name 2, .lines 3 (skipped), .event_metadata 4
# and .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2,
# .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .str_value 5,
# .ref_value 7 (the id of a stat metadata whose name is the string).


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of each field of the message in buf[lo:hi]; a
    varint's value is an int, a length-delimited one a (start, end) pair,
    a fixed-width one None."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, entry):
    """The value of one protobuf map entry."""
    return next((v for num, v in _fields(buf, *entry) if num == 2), (0, 0))


def op_paths(raw: bytes, chips: Sequence[int]) -> Dict[str, str]:
    """Op name -> its metadata path on the device planes of ``chips``: the
    ``tf_op`` stat of the op's event metadata (``<op_name path>:``), or
    ``""`` for an op XLA made without metadata."""
    buf = memoryview(raw)
    paths: Dict[str, str] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        fields = list(_fields(buf, *plane))
        m = DEVICE_PLANE.match(next((_text(buf, v) for n, v in fields if n == 2), ""))
        if not (m and int(m.group(1)) in chips):
            continue
        stat_names: Dict[int, str] = {}
        for n, entry in fields:
            if n == 5:
                sm = dict(_fields(buf, *_map_value(buf, entry)))
                stat_names[sm.get(1, 0)] = _text(buf, sm[2]) if 2 in sm else ""
        path_ids = {i for i, name in stat_names.items() if name == PATH_STAT}
        for n, entry in fields:
            if n != 4:
                continue
            op, path = "", ""
            for f, v in _fields(buf, *_map_value(buf, entry)):
                if f == 2:
                    op = op_name(_text(buf, v))
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in path_ids:
                        path = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            paths.setdefault(op, path)
    return paths


def reduce_trace(trace_dir: str, chips: Sequence[int]) -> dict:
    """``reduce_scopes`` of the newest trace under ``trace_dir``."""
    device, paths, engine, window, in_flight = read_xplane(find_xplane(trace_dir), chips)
    return reduce_scopes(device, paths, engine, window, in_flight=in_flight)


def window_of(ctx) -> Optional[dict]:
    """The reduction of the run's traced window, or None where the run
    left no trace; read on the first call and kept in ``ctx``, which the
    harness builds once per window."""
    if "program_scopes" not in ctx:
        import jax

        from bench.run import TRACE_DIR

        chips = [d.id for d in jax.devices()[: ctx["chips"]]]
        try:
            ctx["program_scopes"] = reduce_trace(str(TRACE_DIR), chips)
        except (FileNotFoundError, ValueError):
            ctx["program_scopes"] = None
    return ctx["program_scopes"]


def phase_ms(ctx, metric: str) -> Optional[float]:
    """Milliseconds per cloud interval of the phase ``metric`` reads;
    None where no op of the window carries a scope."""
    red = window_of(ctx)
    if red is None or not red["scoped"]:
        return None
    return 1000.0 * red["phases"].get(PHASES[metric], 0.0) / ctx["intervals"]


def idle_ms(ctx, kind: str) -> Optional[float]:
    """Milliseconds per cloud interval of device idle of one ``kind``
    (``data_wait`` or ``engine``); None where the window holds no engine
    span."""
    red = window_of(ctx)
    if red is None or not red["host_spans"]:
        return None
    return 1000.0 * red["idle"].get(kind, 0.0) / ctx["intervals"]


if __name__ == "__main__":
    import json
    import sys
    from pathlib import Path

    trace_dir = sys.argv[1] if len(sys.argv) > 1 else str(Path(__file__).resolve().parents[1] / ".bench_trace")
    chips = list(range(int(sys.argv[2]) if len(sys.argv) > 2 else 1))
    print(json.dumps(reduce_trace(trace_dir, chips), indent=1))
