"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers ask for. Reads with ``jax.profiler.ProfileData`` and nothing else.

What a v5e trace looks like (see PERF.md, "Reading a trace"): one plane
``/device:TPU:<n>`` per chip; on it the line ``XLA Modules`` has one event
per execution of a compiled program, named ``<module>(<fingerprint>)``, and
the line ``XLA Ops`` has one event per operation inside them. The host's
threads are lines of the plane ``/host:CPU``; the benchmark's own
``TraceAnnotation`` spans (``bench.*``) land there, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


class Trace:
    """``devices``: {chip: {"ops": [(name, start_s, dur_s)], "modules":
    [...]}}; ``host``: the benchmark's own spans [(name, start_s, dur_s)];
    ``window``: (start_s, end_s) of the span ``bench.window``, or the
    extent of the device events when that span is missing."""

    def __init__(self, devices, host, window):
        self.devices, self.host, self.window = devices, host, window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events if e.name.startswith("bench.")]
    marks = [(s, s + d) for n, s, d in host if n == "bench.window"]
    if marks:
        window = max(marks, key=lambda w: w[1] - w[0])
    else:
        ev = [e for d in devices.values() for e in d["ops"] + d["modules"]]
        window = ((min(s for _, s, _ in ev), max(s + d for _, s, d in ev))
                  if ev else (0.0, 0.0))
    return Trace(devices, host, window)


_LAYOUT = re.compile(r"\{[^}]*\}")


def short_op(name: str, width: int = 96) -> str:
    """An operation's event is named by its whole HLO text
    (``%fusion.48 = bf16[4096,16,16,128]{3,2,1,0:T(8,128)(2,1)} fusion(...``):
    keep the name, the result's shape without its layout, and the start of
    the call."""
    return _LAYOUT.sub("", name)[:width]


def module_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return _FINGERPRINT.sub("", event_name)


def _clip(events, window):
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def busy_intervals(ops, window):
    """Union of the operations' intervals inside the window, merged."""
    spans = sorted((a, b) for _, a, b in _clip(ops, window))
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    per = [sum(b - a for a, b in busy_intervals(d["ops"], trace.window))
           for d in trace.devices.values()]
    return sum(per) / len(per)


def module_durations(trace: Trace, name: str):
    """Device durations (s) of every execution of module ``name`` that lies
    wholly inside the window, over all chips."""
    lo, hi = trace.window
    return [d for dev in trace.devices.values()
            for n, s, d in dev["modules"]
            if module_name(n) == name and s >= lo and s + d <= hi]


def module_names(trace: Trace):
    return sorted({module_name(n) for dev in trace.devices.values()
                   for n, _, _ in dev["modules"]})


def busiest_module(trace: Trace):
    """The program that took most device time in the window (in a training
    cell: the step), or None."""
    names = module_names(trace)
    if not names:
        return None
    return max(names, key=lambda n: sum(module_durations(trace, n)))


def op_seconds(trace: Trace, match) -> float:
    """Device seconds of the operations whose name ``match`` accepts,
    inside the window, averaged over the chips."""
    if not trace.devices:
        return 0.0
    per = [sum(b - a for n, a, b in _clip(d["ops"], trace.window)
               if match(n)) for d in trace.devices.values()]
    return sum(per) / len(per)


def op_durations(trace: Trace, match):
    lo, hi = trace.window
    return [d for dev in trace.devices.values() for n, s, d in dev["ops"]
            if s >= lo and s + d <= hi and match(n)]


def top_ops(trace: Trace, k: int = 10):
    """[[operation, seconds]]: the operations that took most device time
    in the window, summed by name, on the busiest chip."""
    best = []
    for dev in trace.devices.values():
        tot = defaultdict(float)
        for n, a, b in _clip(dev["ops"], trace.window):
            tot[n] += b - a
        rows = sorted(tot.items(), key=lambda kv: -kv[1])
        if sum(tot.values()) > sum(v for _, v in best):
            best = rows
    return [[short_op(n), v] for n, v in best[:k]]


def idle_gaps(trace: Trace, k: int = 10):
    """[[what, seconds]]: the device's idle time inside the window on chip
    0, summed by what bracketed each gap: the benchmark's own host span
    that covers most of it if there is one, else the programs that ran
    before and after it (``jit_step -> jit_prefill``)."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    busy = busy_intervals(dev["ops"], trace.window)
    mods = sorted((s, s + d, module_name(n)) for n, s, d in dev["modules"])
    spans = [(s, s + d, n) for n, s, d in trace.host if n != "bench.window"]
    lo, hi = trace.window
    edges = [(lo, lo)] + [tuple(x) for x in busy] + [(hi, hi)]
    tot = defaultdict(float)
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b - a <= 0:
            continue
        label, cover = None, 0.0
        for s, e, n in spans:
            c = min(e, b) - max(s, a)
            if c > cover and c >= 0.5 * (b - a):
                label, cover = n, c
        if label is None:
            before = next((n for s, e, n in reversed(mods) if s <= a), "start")
            after = next((n for s, e, n in mods if e >= b), "end")
            label = f"{before} -> {after}"
        tot[label] += b - a
    rows = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[n, v] for n, v in rows[:k]]


def catalog(trace: Trace, k: int = 25) -> dict:
    """What is in a trace, for a reader who has not seen one: chips,
    programs with their counts and median device time, the operations that
    took most time. Written beside a traced run's other records."""
    from benchmark.harness.stats import median

    mods = {}
    for name in module_names(trace):
        ds = module_durations(trace, name)
        if ds:
            mods[name] = {"executions": len(ds), "median_s": median(ds)}
    return {"chips": sorted(trace.devices), "window_s": trace.window_s,
            "busy_s": busy_seconds(trace), "modules": mods,
            "host_spans": sorted({n for n, _, _ in trace.host}),
            "top_ops": top_ops(trace, k), "idle_gaps": idle_gaps(trace, k)}
