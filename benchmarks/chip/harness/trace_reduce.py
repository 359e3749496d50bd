"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX's own
``jax.profiler.ProfileData`` (no TensorFlow, no TensorBoard).

What is read: the device planes (``/device:TPU:n``), in each the line of
XLA ops (busy intervals, time per op) and the line of XLA modules (time per
jitted program), and the host planes (what the host's runtime threads were
doing during the device's idle gaps).

* busy: the union of the intervals in which an op ran on the device; idle
  share = 1 - busy / window, where the window is the span from the first to
  the last event of the whole trace, host threads included.
* time per op is SELF time: an op that encloses others on its line (a
  ``while`` round its body, a fusion round its parts) is charged only what
  its children leave, so the times of all ops add up to busy.
* every op is also charged to the program (the event of the modules line)
  inside which it started, so that a reader can ask for a kernel's time in
  the decode program alone: a prefill runs the same expert matmuls, and
  its calls are not a decode step's.
* an idle gap is labelled with the host event that covers most of it, or
  ``host: no runtime call`` when none does — Python, the scheduler, a wait
  for a request.

This module imports JAX but touches no backend; ``python -m
harness.trace_reduce <dir-or-file>`` prints the reduction of one trace, and
``--cut`` writes a cut-down text-proto copy (tests/fixture).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from bisect import bisect_right
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_HOST_EVENT = "host: no runtime call"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(find_xplane(path))


def _events(line) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name), sorted by start then longest first."""
    ev = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
           e.name) for e in line.events]
    ev.sort(key=lambda x: (x[0], -x[1]))
    return ev


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: list[tuple[float, float, str]]) -> list[float]:
    """Duration of each event less what the events it directly encloses
    take (events sorted as :func:`_events` sorts them)."""
    selfs = [b - a for a, b, _ in events]
    stack: list[int] = []
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][1]:
            selfs[stack[-1]] -= b - a
        stack.append(i)
    return selfs


def short_name(op: str, limit: int = 96) -> str:
    """An op's name for a table: on a TPU an op's event carries its whole
    HLO text; kept are its name, result type and kind ("%copy.109 =
    bf16[32,129,8,128,128] copy")."""
    m = re.match(r"^(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])[^ ]* .*?([a-z][a-z-]*)\(",
                 op)
    text = f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else op
    if "tpu_custom_call" in op:
        text += " [tpu_custom_call]"
    return text[:limit]


def reduce(path: str) -> dict:
    """The reduction of one trace; times in seconds."""
    pd = load(path)
    lo, hi = float("inf"), float("-inf")
    device_planes, host_events = [], []
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            ev = _events(line)
            if not ev:
                continue
            lo = min(lo, ev[0][0])
            hi = max(hi, max(b for _, b, _ in ev))
            if is_dev:
                lines[line.name] = ev
            elif plane.name.startswith("/host:"):
                host_events.extend(ev)
        if is_dev:
            device_planes.append((plane.name, lines))
    if not device_planes:
        raise ValueError(
            "no device plane in the trace (planes: "
            f"{[p.name for p in pd.planes]}): nothing ran on a TPU")
    window = (hi - lo) / 1e9
    host_events.sort()

    # op -> [calls, self ns->s, total s, {program: [calls, self s]}]
    busy_s, ops, programs = [], defaultdict(lambda: [0, 0.0, 0.0, {}]), {}
    gaps: list[tuple[float, float]] = []
    for name, lines in device_planes:
        if OPS_LINE not in lines:
            raise ValueError(f"{name} has no {OPS_LINE!r} line, only "
                             f"{sorted(lines)}")
        ev = lines[OPS_LINE]
        merged = union([(a, b) for a, b, _ in ev])
        busy_s.append(sum(b - a for a, b in merged) / 1e9)
        mods = sorted(lines.get(MODULES_LINE, []))
        mod_starts = [m[0] for m in mods]
        for (a, b, op), s in zip(ev, self_times(ev)):
            rec = ops[op]
            rec[0] += 1
            rec[1] += s / 1e9
            rec[2] += (b - a) / 1e9
            i = bisect_right(mod_starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else ""
            per = rec[3].setdefault(prog, [0, 0.0])
            per[0] += 1
            per[1] += s / 1e9
        edges = [lo, *[x for ab in merged for x in ab], hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for a, b, prog in lines.get(MODULES_LINE, []):
            programs.setdefault(prog, []).append((b - a) / 1e9)

    def label(a: float, b: float) -> str:
        best, cover = NO_HOST_EVENT, 0.0
        for s, e, n in host_events:
            if s >= b:
                break
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        return best if cover >= 0.5 * (b - a) else NO_HOST_EVENT

    gaps.sort(key=lambda g: g[0] - g[1])
    by_label: dict[str, float] = defaultdict(float)
    for a, b in gaps[:200]:
        by_label[label(a, b)] += (b - a) / 1e9
    rest = sum(b - a for a, b in gaps[200:]) / 1e9
    if rest:
        by_label["(gaps beyond the 200 longest)"] += rest
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])
    n_dev = len(device_planes)
    return {
        "window_s": window,
        "busy_s": sum(busy_s) / n_dev,
        "busy_s_per_device": busy_s,
        "devices": n_dev,
        "ops": {k: {"count": v[0], "self_s": v[1], "total_s": v[2],
                    "in_program": v[3]}
                for k, v in top_ops},
        "programs": programs,
        "breakdown": {
            "device_ops": [[short_name(k), v[1] / n_dev]
                           for k, v in top_ops[:10]],
            "idle_gaps": sorted(([k, v / n_dev] for k, v in by_label.items()),
                                key=lambda kv: -kv[1])[:10],
        },
    }


def op_time(red: dict, pattern: str, program: str | None = None
            ) -> tuple[float, int]:
    """Summed self time and count of the ops whose name matches
    ``pattern`` — with ``program``, of their calls inside the programs
    whose name matches that."""
    rx = re.compile(pattern)
    hit = [v for k, v in red["ops"].items() if rx.search(k)]
    if program is None:
        return sum(v["self_s"] for v in hit), sum(v["count"] for v in hit)
    px = re.compile(program)
    per = [cs for v in hit for prog, cs in v["in_program"].items()
           if px.search(prog)]
    return sum(s for _, s in per), sum(n for n, _ in per)


def program_durations(red: dict, pattern: str) -> list[float]:
    rx = re.compile(pattern)
    return [d for k, ds in red["programs"].items() if rx.search(k)
            for d in ds]


def cut_to_text(path: str, out: str, per_line: int = 400) -> None:
    """A cut-down copy of a trace as an XSpace text proto: device planes
    whole lines cut to their first ``per_line`` events, host planes to the
    events that overlap them, clipped to their span.  Small enough to keep in git."""
    pd = load(path)
    chunks, span = [], [float("inf"), float("-inf")]
    planes = list(pd.planes)
    # times are written relative to the trace's first event (picoseconds of
    # an absolute clock would not fit the proto's 64 bits)
    base = min((float(e.start_ns) for pl in planes for ln in pl.lines
                for e in ln.events), default=0.0)
    for want_dev in (True, False):
        for pid, plane in enumerate(planes):
            is_dev = bool(DEVICE_PLANE.match(plane.name))
            if is_dev != want_dev or not (
                    is_dev or plane.name.startswith("/host:")):
                continue
            meta: dict[str, int] = {}
            body = []
            for lid, line in enumerate(plane.lines):
                ev = _events(line)
                if is_dev:
                    ev = ev[:per_line]
                    if ev:
                        span[0] = min(span[0], ev[0][0])
                        span[1] = max(span[1], max(b for _, b, _ in ev))
                else:
                    ev = [(max(a, span[0]), min(b, span[1]), n)
                          for a, b, n in ev
                          if b > span[0] and a < span[1]][:per_line]
                if not ev:
                    continue
                evs = "".join(
                    f" events {{ metadata_id: "
                    f"{meta.setdefault(n, len(meta) + 1)} "
                    f"offset_ps: {int((a - base) * 1000)} "
                    f"duration_ps: {int((b - a) * 1000)} }}\n"
                    for a, b, n in ev)
                body.append(f" lines {{ id: {lid + 1} "
                            f"name: {json.dumps(line.name)}\n{evs} }}\n")
            metas = "".join(
                f" event_metadata {{ key: {i} value {{ id: {i} "
                f"name: {json.dumps(n)} }} }}\n" for n, i in meta.items())
            chunks.append(f"planes {{ id: {pid + 1} "
                          f"name: {json.dumps(plane.name)}\n"
                          f"{''.join(body)}{metas}}}\n")
    with open(out, "w") as f:
        f.write("".join(chunks))


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--cut":
        cut_to_text(sys.argv[2], sys.argv[3])
    elif len(sys.argv) >= 4 and sys.argv[2] == "--json":
        with open(sys.argv[3], "w") as f:
            json.dump(reduce(sys.argv[1]), f)
    else:
        r = reduce(sys.argv[1])
        r["ops"] = dict(list(r["ops"].items())[:40])
        r["programs"] = {k: {"count": len(v), "total_s": sum(v)}
                         for k, v in r["programs"].items()}
        print(json.dumps(r, indent=1))
