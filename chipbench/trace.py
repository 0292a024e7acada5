"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run writes one ``.xplane.pb``. In it, each TPU is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
the chip ran; the host's planes (``/host:...``) hold the harness's spans,
written as ``jax.profiler.TraceAnnotation``, on the same clock. The
window is the host span named ``window``.

- busy: the union of the ``XLA Ops`` intervals inside the window, per
  chip, averaged over the chips; idle share is 1 - busy / window;
- device ops: the operations that took most time inside the window;
- idle gaps: the window's idle time on the first chip, each gap named by
  the innermost harness span open over it, piece by piece (the
  runtime's own host events, on other threads, name none).
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
TOP = 10


def reduce_dir(trace_dir: str, *, n_devices: int, names: set[str] | None = None) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(paths[0]), n_devices=n_devices, names=names)


def idle_share(reduced: dict | None) -> float | None:
    """Percent of the window in which no operation ran on the device."""
    if reduced is None or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _pieces(named: list[tuple[int, int, str]], w0: int, w1: int):
    """Cut the window at every span's start and end; yield each piece as
    ``(start, end, name)`` of the innermost span open over it (the one
    that began last), or ``(no span)``."""
    edges = sorted({w0, w1, *(min(max(t, w0), w1) for s, e, _ in named for t in (s, e))})
    starts = sorted(named)
    active: list[tuple[int, int, str]] = []   # heap of (-start, end, name)
    j = 0
    for p0, p1 in zip(edges, edges[1:]):
        while j < len(starts) and starts[j][0] <= p0:
            heapq.heappush(active, (-starts[j][0], starts[j][1], starts[j][2]))
            j += 1
        while active and active[0][1] <= p0:
            heapq.heappop(active)
        yield p0, p1, active[0][2] if active else "(no span)"


def reduce_profile(pd, *, n_devices: int, names: set[str] | None = None) -> dict:
    """``names``: the harness's span names, the only host events that may
    name an idle gap (None: every host event)."""
    spans: list[tuple[int, int, str]] = []
    devices: dict[int, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                raise RuntimeError(f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
            devices[int(m.group(1))] = [(ev.start_ns, ev.end_ns, ev.name)
                                        for ev in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.end_ns, ev.name) for ev in line.events)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span in the trace, found {len(windows)}")
    w0, w1 = windows[0]
    used = sorted(devices)[:n_devices]
    if len(used) < n_devices:
        raise RuntimeError(f"trace holds {len(used)} TPU planes, the cell uses {n_devices}")

    busy_ns, op_ns = [], defaultdict(int)
    gaps: list[tuple[int, int]] = []
    for i, dev in enumerate(used):
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in devices[dev] if e > w0 and s < w1]
        for s, e, n in clipped:
            op_ns[n] += e - s
        merged = _union([(s, e) for s, e, _ in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        if i == 0:
            edge = w0
            for s, e in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if edge < w1:
                gaps.append((edge, w1))

    named = [(s, e, n) for s, e, n in spans
             if n != WINDOW and (names is None or n in names) and s < w1 and e > w0]
    idle = defaultdict(int)
    pieces = iter(_pieces(named, w0, w1))
    piece = next(pieces, None)
    for s, e in gaps:
        while piece is not None and s < e:
            p0, p1, name = piece
            if p1 <= s:
                piece = next(pieces, None)
                continue
            cut = min(e, p1)
            idle[name] += cut - s
            s = cut

    def top(d):
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": top(op_ns),
        "idle_gaps": top(idle),
    }
