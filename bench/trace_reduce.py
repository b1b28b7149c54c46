"""Reduce a profiler trace (``*.xplane.pb``) to device busy time, the
device operations that took most time, and the longest idle gaps with
what the host was doing in them.

On a TPU the trace holds one plane per chip, named ``/device:TPU:<n>``,
whose line ``XLA Ops`` has one event per operation run on the chip,
named by its HLO text (``%fusion.3 = bf16[...] fusion(...)``). The host
plane ``/host:CPU`` has a line per thread; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) are events there. Busy time is the
union of the operation intervals inside the traced window; idle is the
rest of the window.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def op_name(hlo_text: str) -> str:
    """``%convolution_tanh_fusion.1 = bf16[..] fusion(..)`` ->
    ``convolution_tanh_fusion.1``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def read(path: str) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns, op)]}, "host":
    [(start_ns, end_ns, name)]}`` from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = sorted(
                        (s, s + d, op_name(n)) for n, s, d in _events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((s, s + d, n) for n, s, d in _events(line))
    return {"devices": devices, "host": host}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(intervals, lo: float, hi: float) -> dict:
    """Seconds of each operation's own time in ``[lo, hi)``: its
    interval less those of the operations nested inside it (a ``while``
    op holds the operations of its body on the same line)."""
    out: dict = defaultdict(float)
    stack: list[list] = []          # [end, name, own time so far]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            out[name] += own * 1e-9

    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def host_activity(host, t: float) -> str:
    """Name of the shortest host event that spans time ``t``."""
    best, best_len = "none", float("inf")
    for s, e, name in host:
        if s <= t < e and e - s < best_len:
            best, best_len = name, e - s
    return best


def window_of(host, span_name: str) -> tuple[float, float] | None:
    """``[start, end)`` of the host span called ``span_name``."""
    for s, e, name in host:
        if name == span_name:
            return s, e
    return None


def reduce(trace: dict, lo: float | None = None, hi: float | None = None,
           top: int = 10) -> dict:
    """Busy seconds (averaged over the chips), window seconds, the
    ``top`` device operations by their own time (averaged over the
    chips; nested operations are not counted twice), and the ``top``
    longest idle gaps named by the host activity at their midpoint.
    Without a window, the span of all device operations is taken."""
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device operations")
    if lo is None or hi is None:
        lo = min(iv[0][0] for iv in devs.values() if iv)
        hi = max(max(e for _, e, _ in iv) for iv in devs.values() if iv)
    busy_ns, per_op = 0.0, defaultdict(float)
    idle = []
    for ivs in devs.values():
        merged = union(ivs, lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for name, sec in self_times(ivs, lo, hi).items():
            per_op[name] += sec / len(devs)
        idle.extend(gaps(merged, lo, hi))
    idle.sort(key=lambda g: g[0] - g[1])
    host = trace["host"]
    return {
        "busy_s": busy_ns / len(devs) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[host_activity(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }
