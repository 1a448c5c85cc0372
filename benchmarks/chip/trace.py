"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the traced window, device busy time (the union of the
intervals in which an operation ran), time per device operation, and the
device's idle gaps named by what the host was doing in them.

The window is the host span named ``WINDOW`` that the harness opens around
the measured loop; host spans inside it (``dispatch``, ``drain``, ``tick``
...) name the idle gaps.  Device operations are the events of each
``/device:TPU:<n>`` plane's "XLA Ops" line (nested: a loop's event spans
its body's); the device is busy while a program runs (its "XLA Modules"
line) or an operation does.  All times are clipped to the window.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    window: tuple[float, float]                 # seconds, trace clock
    # per chip: (start, end, name) of each device op inside the window
    ops: list[list[tuple[float, float, str]]] = field(default_factory=list)
    # per chip: (start, end, name) of each program execution
    modules: list[list[tuple[float, float, str]]] = field(
        default_factory=list)
    # host spans inside the window: (start, end, name)
    spans: list[tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices, modules = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9,
                                 ev.name))
        elif plane.name.startswith("/device:TPU:"):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name not in lines:
                    continue
                for ev in line.events:
                    lines[line.name].append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
            devices.append(lines[OPS_LINE])
            modules.append(lines[MODULES_LINE])
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no host span {WINDOW!r} in {path}")
    w0, w1 = wins[0]
    clip = lambda evs: [(max(s, w0), min(e, w1), n) for s, e, n in evs  # noqa
                        if e > w0 and s < w1]
    return Trace(window=(w0, w1),
                 ops=[sorted(clip(d)) for d in devices],
                 modules=[sorted(clip(m)) for m in modules],
                 spans=[x for x in clip(host) if x[2] != WINDOW])


def merged(ops) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e, _ in ops:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy(trace: Trace, chip: int) -> list[tuple[float, float]]:
    mods = trace.modules[chip] if trace.modules else []
    return merged(sorted(trace.ops[chip] + mods))


def busy_s(trace: Trace) -> float:
    """Seconds in which a program or an operation ran, averaged over the
    chips."""
    if not trace.ops:
        return 0.0
    return sum(sum(e - s for s, e in _busy(trace, c))
               for c in range(len(trace.ops))) / len(trace.ops)


def op_seconds(trace: Trace, match) -> float:
    """Device seconds of the ops whose name ``match`` accepts, summed over
    chips and averaged."""
    if not trace.ops:
        return 0.0
    return sum(sum(e - s for s, e, n in d if match(n))
               for d in trace.ops) / len(trace.ops)


def short_name(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep what names
    it: the instruction name and, for a kernel, its target."""
    head = name.split(" = ", 1)[0]
    if 'custom_call_target="' in name:
        head += " " + name.split('custom_call_target="', 1)[1].split('"')[0]
    return head


def leaf_ops(ops) -> list[tuple[float, float, str]]:
    """The ops that hold no other op (a loop's event spans its body's)."""
    out = []
    for i, (s, e, n) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue
        out.append((s, e, n))
    return out


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The k device ops, loops left out, that took most time."""
    total: dict[str, float] = {}
    for d in trace.ops:
        for s, e, n in leaf_ops(d):
            n = short_name(n)
            total[n] = total.get(n, 0.0) + (e - s) / len(trace.ops)
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """Idle seconds of the first chip, summed by the innermost host span
    under each gap's midpoint ("no span" where the host was in none)."""
    if not trace.ops:
        return []
    busy = _busy(trace, 0)
    edges = [trace.window[0]] + [x for b in busy for x in b] + [
        trace.window[1]]
    by: dict[str, float] = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        inner = [x for x in trace.spans if x[0] <= mid <= x[1]]
        name = (min(inner, key=lambda x: x[1] - x[0])[2] if inner
                else "no span")
        by[name] = by.get(name, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(by.items(), key=lambda x: -x[1])[:k]]
