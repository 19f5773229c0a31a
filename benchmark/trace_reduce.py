"""From a profiler trace to numbers. Kept with the benchmark so that every PR
computes the same number in the same way.

A trace, here, is a plain structure (what ``load_xplane`` returns and what
``testdata/`` holds as JSON)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are those named ``/device:TPU:<n>``. Their ``XLA Ops`` line holds
one event per operation the chip ran, ``XLA Modules`` one per launched program
(a jitted step, a decode launch). Host spans that the benchmark writes with
``jax.profiler.TraceAnnotation`` (named ``bench:<span>``) are on the host
planes, on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
# A collective by its opcode, or by the name XLA gives the instruction: the
# opcode's own (`all-reduce.<n>`) or, where the exchange reduces ONE array, the
# JAX primitive's (`psum.<n>`: a one-leaf bucket's all-reduce, two of the
# fourteen in gpt2m-train-dp4 and a quarter of the wire's time; PERF.md
# section 3). Those two are the names read in that cell's trace; what JAX calls
# the other collectives' instructions no cell has shown yet.
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all"
    r"|psum)"
)


def find_xplane(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(text):
    """The trace names an op by its whole HLO line (``%fusion.4 = f32[...]
    fusion(...)``); keep the instruction's name, and for a custom call its
    target, which is how a Pallas kernel is told from the rest."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if name.startswith("custom-call"):
        m = re.search(r'custom_call_target="([^"]+)"', text)
        if m:
            name += ":" + m.group(1)
    return name


def load_xplane(path):
    """Read an ``.xplane.pb`` into the plain structure. Device planes keep
    their ops, async ops and modules lines; host planes keep only ``bench:``
    spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    continue
                events = [[op_name(e.name), int(e.start_ns),
                           int(e.duration_ns)] for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    found = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            found.append((int(m.group(1)), plane))
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def line_events(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def host_spans(trace):
    """``[(name, start_ns, end_ns)]`` of the benchmark's own spans."""
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], start, start + dur))
    return sorted(spans, key=lambda s: s[1])


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The part of merged ``intervals`` not covered by merged ``holes``."""
    out = []
    holes = list(holes)
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def as_intervals(events):
    return [(s, s + d) for _, s, d in events]


def is_collective(name):
    return bool(COLLECTIVE.match(name))


def steady_window(modules, match=None, skip=1):
    """The traced steady window on one chip: from the start of launch
    ``skip`` (the first may still be catching up) to the end of the last
    launch. ``match`` selects the launches by name."""
    picked = [e for e in modules if match is None or match(e[0])]
    if len(picked) > skip:
        picked = picked[skip:]
    if not picked:
        return None, []
    return (picked[0][1], picked[-1][1] + picked[-1][2]), picked


def busy_and_window(trace, match=None, skip=1):
    """Per chip ``(busy_ns, window_ns)`` over the steady window."""
    out = []
    for plane in device_planes(trace):
        window, _ = steady_window(line_events(plane, MODULES_LINE), match,
                                  skip)
        if window is None:
            continue
        ops = merge(clip(as_intervals(line_events(plane, OPS_LINE)),
                         *window))
        out.append((total(ops), window[1] - window[0]))
    return out


def per_launch(plane, match=None, skip=1):
    """For each launch in the steady window of one chip: its period (start to
    next start; the last has none), its ops, and its busy time."""
    modules = line_events(plane, MODULES_LINE)
    window, launches = steady_window(modules, match, skip)
    if window is None:
        return []
    ops = line_events(plane, OPS_LINE)
    out, j = [], 0
    for i, (name, start, dur) in enumerate(launches):
        end = start + dur
        while j < len(ops) and ops[j][1] + ops[j][2] <= start:
            j += 1
        k, mine = j, []
        while k < len(ops) and ops[k][1] < end:
            mine.append(ops[k])
            k += 1
        nxt = launches[i + 1][1] if i + 1 < len(launches) else None
        out.append({
            "name": name, "start": start, "end": end,
            "period": None if nxt is None else nxt - start,
            "ops": mine,
            "busy": total(merge(clip(as_intervals(mine), start, end))),
        })
    return out


def launch_busy_ms(trace, match):
    """Median busy time of one launch on chip 0, in milliseconds."""
    planes = device_planes(trace)
    launches = per_launch(planes[0], match) if planes else []
    if not launches:
        return None
    return median([l["busy"] for l in launches]) / 1e6


def idle_share_percent(trace, match):
    """1 - busy / window over the steady window of chip 0, in percent."""
    per_chip = busy_and_window(trace, match)
    if not per_chip:
        return None
    busy, window = per_chip[0]
    return 100.0 * (1.0 - busy / window)


def collective_times(launch):
    """``(summed, exposed)`` ns of the collectives of one launch: summed
    durations, and the part during which no other op runs on that chip."""
    coll = [e for e in launch["ops"] if is_collective(e[0])]
    rest = merge(as_intervals(
        [e for e in launch["ops"] if not is_collective(e[0])]
    ))
    merged = merge(as_intervals(coll))
    return sum(e[2] for e in coll), total(subtract(merged, rest))


def named_ops_ns(trace, match, mine):
    """``(median ns per step, median calls per step)`` of the ops of chip 0
    whose instruction name ``mine`` accepts (a kernel is told by its name:
    ``attention.<n>``, ``flash_bwd.<n>``, ``moe_combine.<n>``), over the
    steady launches that hold one; ``None`` where none does."""
    planes = device_planes(trace)
    if not planes:
        return None
    per_step = []
    for launch in per_launch(planes[0], match):
        found = [e[2] for e in launch["ops"] if mine(e[0])]
        if found:
            per_step.append((sum(found), len(found)))
    if not per_step:
        return None
    return (median([t for t, _ in per_step]),
            median([n for _, n in per_step]))


def top_ops(trace, window_match=None, skip=1, n=10):
    """The device operations that took most time on chip 0, in seconds."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = planes[0]
    window, _ = steady_window(line_events(plane, MODULES_LINE), window_match,
                              skip)
    if window is None:
        return []
    sums = {}
    for name, start, dur in line_events(plane, OPS_LINE):
        if start >= window[0] and start + dur <= window[1]:
            sums[name] = sums.get(name, 0) + dur
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace, window_match=None, skip=1, n=10):
    """The longest idle gaps of chip 0, each named by the benchmark span the
    host was in at the gap's middle (``between`` if in none), in seconds."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = planes[0]
    window, _ = steady_window(line_events(plane, MODULES_LINE), window_match,
                              skip)
    if window is None:
        return []
    busy = merge(clip(as_intervals(line_events(plane, OPS_LINE)), *window))
    gaps = subtract([window], busy)
    spans = host_spans(trace)
    sums = {}
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [s for s in spans if s[1] <= mid < s[2]]
        # the innermost span: the one that started last
        name = max(inside, key=lambda s: s[1])[0] if inside else "between"
        sums[name] = sums.get(name, 0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def median(values):
    return statistics.median(values) if values else None
