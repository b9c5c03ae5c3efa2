"""Reduce a JAX profiler trace to the numbers the readers take.

The traced run records its whole serving window with ``jax.profiler``,
the engine's host spans (``repro.obs`` ``Tracer`` with
``jax_annotations=True``: ``flush``, ``pack``, ``dispatch``,
``flag_readback``, ``result_pickup``, ``seal``, ``merge``) and the
benchmark's own ``bench_window`` span on the profiler's clock.  From the
``.xplane.pb`` file (``jax.profiler.ProfileData``):

* device planes (``/device:TPU:n``): the ``XLA Modules`` line holds one
  event per program run (``jit_query_step(...)``), the ``XLA Ops`` line
  one per operation;
* busy time is the union of operation intervals inside the window, per
  chip, averaged over chips; idle is the rest of the window;
* each idle gap is put down to the innermost host span around its
  middle, or to ``client_wait`` where the host ran none (the open loop
  waiting for arrivals).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "bench_window"
HOST_SPANS = ("flush", "pack", "dispatch", "flag_readback", "result_pickup",
              "seal", "merge", "spill", "cold_fetch", "bench_submit")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict            # program name -> [device seconds, runs]
    device_ops: list         # [[module:op, seconds], ...] longest first
    idle_gaps: list          # [[host span, seconds], ...] longest first
    n_chips: int

    def module(self, prefix: str) -> tuple[float, int]:
        """Device seconds and runs of programs whose name starts with
        ``prefix`` (``jit_query_step``)."""
        t = n = 0
        for name, (s, c) in self.modules.items():
            if name.startswith(prefix):
                t += s
                n += c
        return t, n


def _union(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    return paths[0]


def reduce(trace, top: int = 10) -> Summary:
    """``trace``: an ``.xplane.pb`` path or a ``ProfileData``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    spans, window = [], None
    devices = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices.append(lines)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in HOST_SPANS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in the trace")
    if not devices:
        raise RuntimeError("no device plane with XLA Ops in the trace")
    w0, w1 = window
    modules: dict = {}
    ops: dict = {}
    gaps: dict = {}
    busy = 0.0
    spans.sort()
    span_starts = [sp[0] for sp in spans]
    for lines in devices:
        mods = []
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else ()):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            name = _SUFFIX.sub("", ev.name)
            acc = modules.setdefault(name, [0.0, 0])
            acc[0] += (e - s) * 1e-9
            acc[1] += 1
            mods.append((ev.start_ns, ev.end_ns, name))
        mods.sort()
        starts = [m[0] for m in mods]
        iv = []
        for ev in lines["XLA Ops"].events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            iv.append((s, e))
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = mods[j][2] if j >= 0 and ev.start_ns < mods[j][1] else "?"
            key = f"{mod}:{ev.name.split(' = ')[0]}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        merged = _union(iv)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                label = _label(spans, span_starts, (g0 + g1) / 2)
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0) * 1e-9
    n = len(devices)
    by_time = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    by_gap = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
        modules={k: [v[0] / n, v[1]] for k, v in modules.items()},
        device_ops=[[k, v / n] for k, v in by_time],
        idle_gaps=[[k, v / n] for k, v in by_gap], n_chips=n)


def _label(spans: list, starts: list, t: float, look: int = 256) -> str:
    """The innermost of the host spans (sorted by start) that cover
    time ``t``; spans nest within a flush, so the few hundred that start
    last before ``t`` hold it."""
    best, width = "client_wait", None
    i = bisect.bisect_right(starts, t)
    for s, e, name in spans[max(0, i - look):i]:
        if e >= t and (width is None or e - s < width):
            best, width = name, e - s
    return best
