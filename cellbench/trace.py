"""Reduction of a JAX profiler trace to device busy/idle, time per
executable and per operation, and idle gaps with what the host did.

What a v5e trace holds (looked at by hand, PR 24): plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executable run, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per
HLO operation, NESTED: a ``%while`` holds its body's operations) and
``Async XLA Ops``; plane ``/host:CPU`` with one line per thread holding
the runtime's own spans (``tpu::System::Execute=>Done`` ...).  Nothing
in the program names its steps yet, so an idle gap can only be
attributed to those runtime spans.

The arithmetic works on a plain structure, ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``,
which ``load_xplane`` builds from an ``.xplane.pb`` file and the tests
read from a small recorded sample.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re

TOP = 10
MIN_GAP_S = 0.0002  # shorter idle stretches are launch overhead, not gaps


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        lines = []
        for line in plane.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events
                   if not (host and e.name.startswith("$"))]  # python frames
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Seconds covered by a set of [start_ns, end_ns) intervals."""
    total, end = 0.0, -1.0
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total / 1e9


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float):
    """Idle stretches of [lo, hi) not covered by ``intervals``."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(s, e) for s, e in out if e > s]


def base_name(name: str) -> str:
    """``%fusion.123 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def self_times(events: list[list]) -> dict[str, float]:
    """Seconds per operation name with nested children taken out of
    their parents (a ``while`` keeps only what is not its body)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end_ns, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([base_name(name), start + dur, dur])
    close(float("inf"))
    return out


class TraceSummary:
    def __init__(self, planes: dict):
        devs = [p for p in planes["planes"]
                if p["name"].startswith("/device:") and "CUSTOM" not in p["name"]
                and any(ln["name"] in ("XLA Ops", "XLA Modules")
                        for ln in p["lines"])]
        if not devs:
            raise ValueError("the trace holds no device plane with operations")
        hosts = [p for p in planes["planes"] if p["name"].startswith("/host:")]
        every = [(e[1], e[1] + e[2]) for p in devs + hosts
                 for ln in p["lines"] for e in ln["events"]]
        self.lo, self.hi = min(s for s, _ in every), max(e for _, e in every)
        self.window_s = (self.hi - self.lo) / 1e9
        self.n_devices = len(devs)
        busy, self.modules, self.ops = [], {}, {}
        all_gaps: list[tuple[float, float]] = []
        for p in devs:
            lines = {ln["name"]: ln["events"] for ln in p["lines"]}
            op_events = lines.get("XLA Ops") or lines.get("XLA Modules")
            iv = [(e[1], e[1] + e[2]) for e in op_events]
            busy.append(union_s(iv))
            all_gaps += gaps(iv, self.lo, self.hi)
            for name, _, dur in lines.get("XLA Modules", []):
                key = name.split("(", 1)[0]
                tot, n = self.modules.get(key, (0.0, 0))
                self.modules[key] = (tot + dur / 1e9, n + 1)
            for name, sec in self_times(lines.get("XLA Ops", [])).items():
                self.ops[name] = self.ops.get(name, 0.0) + sec
        self.busy_s = sum(busy) / len(busy)
        self.idle_by_host = self._attribute(all_gaps, hosts)

    def _attribute(self, idle, hosts) -> dict[str, float]:
        """Idle seconds by the host-plane span that overlaps each gap
        most ('unattributed' when none does)."""
        spans = sorted((e[1], e[1] + e[2], e[0]) for p in hosts
                       for ln in p["lines"] for e in ln["events"])
        starts = [s for s, _, _ in spans]
        latest_end = list(itertools.accumulate((e for _, e, _ in spans), max))
        out: dict[str, float] = {}
        for gs, ge in idle:
            if (ge - gs) / 1e9 < MIN_GAP_S:
                continue
            best, best_ov = "unattributed", 0.0
            # only spans that start before the gap ends and, from the
            # first one on, can still be open when it begins
            lo = bisect.bisect_right(latest_end, gs)
            for s, e, name in spans[lo:bisect.bisect_left(starts, ge)]:
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = name, ov
            out[best] = out.get(best, 0.0) + (ge - gs) / 1e9
        return out

    def module_time(self, pattern: str) -> tuple[float, int]:
        """(seconds, runs) of the executables whose name matches."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.modules.items() if rx.search(k)]
        return sum(t for t, _ in hits), sum(n for _, n in hits)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.idle_by_host)}

    def describe(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "devices": self.n_devices,
                "modules": {k: {"seconds": t, "runs": n}
                            for k, (t, n) in sorted(self.modules.items())},
                **self.breakdown()}


def summarize(trace_dir: str) -> TraceSummary:
    return TraceSummary(load_xplane(find_xplane(trace_dir)))
