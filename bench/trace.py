"""Reduce a profiler trace of the measured window to device numbers.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. Its planes are
devices (``/device:TPU:<i>``, whose ``XLA Ops`` line holds one event per
executed HLO op) and the host (``/host:CPU``, one line per thread, holding
the ``TraceAnnotation`` spans the harness puts around the window and the
calls in it). Device and host events share one clock in nanoseconds.

What is read, per chip, inside the window span:

* busy: the union of the op intervals (overlapping ops count once; a
  ``while`` or ``conditional`` op spans the ops of its body, so ops are
  ranked by self time, less their children);
* idle gaps: the window less that union, each named by the harness's host
  span (``bench.*``) that overlaps it most, the innermost on a tie, e.g.
  ``python3:bench.chunk``; gaps shorter than ``SHORT_GAP_S`` lie between the
  ops of one program and are summed under one name;
* collectives: ops whose name is a cross-chip collective, on the ops line
  or in flight on the ``Async XLA Ops`` line, and the part of them during
  which no other op without children runs on that chip (exposed time).

Numbers are averaged over the chips the cell uses.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # async ops from start to done
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|psum", re.IGNORECASE
)

SHORT_GAP_S = 20e-6
SHORT_GAP = "device:gaps_under_20us"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def clip3(ops: Iterable[Tuple[str, float, float]], lo: float, hi: float) -> List[Tuple[str, float, float]]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(hlo_text: str) -> str:
    """An op event's name is its HLO instruction text; keep the instruction
    name before ``=``, without its ``%``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _name_gap(gap: Interval, host: Sequence[Tuple[str, str, float, float]]) -> str:
    if gap[1] - gap[0] < SHORT_GAP_S:
        return SHORT_GAP
    best, best_key = "host:no_bench_span", None
    for thread, name, s, e in host:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        key = (overlap, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = f"{thread}:{name}", key
    return best


def nest(ops: Sequence[Tuple[str, float, float]]) -> List[list]:
    """One chip's ops as executed, where a control-flow op (``while``,
    ``conditional``) spans the ops of its body: [name, start, end, self
    seconds (less its direct children), has no children]."""
    out: List[list] = []
    stack: List[int] = []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
            parent[4] = False
        out.append([n, s, e, e - s, True])
        stack.append(len(out) - 1)
    return out


def reduce_events(
    device: Dict[int, List[Tuple[str, float, float]]],
    host: List[Tuple[str, str, float, float]],
    window: Interval,
    *,
    async_collectives: Dict[int, List[Tuple[str, float, float]]] = None,
    top: int = 10,
) -> dict:
    """Device numbers of one window from raw events (times in seconds).

    ``device``: chip -> [(op name, start, end)] of its executed ops, nested;
    ``async_collectives``: chip -> collectives in flight beside them, from
    start to done; ``host``: [(thread, span name, start, end)]. Returns busy
    and exposed-collective seconds averaged over chips, and the ops with the
    most self time and the idle-gap causes (seconds per chip)."""
    lo, hi = window
    chips = sorted(device)
    if not chips:
        raise ValueError("the trace holds no device ops")
    async_collectives = async_collectives or {}
    busy = []
    exposed = []
    n_coll = 0
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for chip in chips:
        ops = nest(clip3(device[chip], lo, hi))
        in_flight = clip3(async_collectives.get(chip, []), lo, hi)
        all_ops = union([(s, e) for _, s, e, _, _ in ops] + [(s, e) for _, s, e in in_flight])
        busy.append(measure(all_ops))
        for n, _, _, self_s, _ in ops:
            op_time[n] += self_s / len(chips)
        coll = [(s, e) for n, s, e, _, _ in ops if COLLECTIVE.search(n)] + [(s, e) for _, s, e in in_flight]
        n_coll += len(coll)
        # a collective is exposed where no other op without children (no
        # loop or branch that merely encloses it) runs on the chip
        other = union((s, e) for n, s, e, _, leaf in ops if leaf and not COLLECTIVE.search(n))
        exposed.append(measure(subtract(union(coll), other)))
        for gap in subtract([(lo, hi)], all_ops):
            gap_time[_name_gap(gap, host)] += (gap[1] - gap[0]) / len(chips)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": hi - lo,
        "chips": len(chips),
        "busy_s": sum(busy) / len(chips),
        "collective_ops": n_coll,
        "collective_exposed_s": sum(exposed) / len(chips),
        "device_ops": [[n, t] for n, t in by_time(op_time)],
        "idle_gaps": [[n, t] for n, t in by_time(gap_time)],
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, chips: Sequence[int]):
    """(device ops by chip, async collectives by chip, host spans, window)
    from one xplane file, keeping the listed chips only."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[int, list] = {}
    in_flight: Dict[int, list] = {}
    host: list = []
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in chips:
            chip = int(m.group(1))
            ops = device.setdefault(chip, [])
            for line in plane.lines:
                events = ((op_name(ev.name), ev.start_ns * 1e-9, ev.end_ns * 1e-9) for ev in line.events)
                if line.name == OP_LINE:
                    ops.extend(events)
                elif line.name == ASYNC_LINE:
                    in_flight.setdefault(chip, []).extend(e for e in events if COLLECTIVE.search(e[0]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    host.append((line.name, ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return device, in_flight, host, window


def reduce_trace(trace_dir: str, chips: Sequence[int]) -> dict:
    device, in_flight, host, window = read_xplane(find_xplane(trace_dir), chips)
    return reduce_events(device, host, window, async_collectives=in_flight)
