"""From a profiler trace to a step's device time by the program's own scopes.

``trace_reduce.load_xplane`` keeps an operation's instruction name
(``fusion.363``) and drops where it came from. This reader keeps, for every
``XLA Ops`` event of chip 0 inside a launch of the step, its SCOPE PATH: the
``op_name`` JAX gave the operation, which holds flax's module scopes, JAX's
``jvp(``/``transpose(jvp(`` marks and the program's own ``jax.named_scope``
names (``horovod_tpu.trace.STEP_SCOPES``), e.g.
``jit(step)/.../hvd_loss_grad/transpose(jvp(TransformerLM))/block_3/attention/
flash_bwd/while/body/dot_general``. Where the path is found in the trace is in
``op_metadata`` below and in PERF.md section 3.

A scoped trace is the plain structure of ``trace_reduce`` (chip 0 only, its
``XLA Modules`` and ``XLA Ops`` lines) plus two maps, because an instruction's
name is unique inside one compiled program::

    {"planes": [{"name": "/device:TPU:0", "lines": [...]}],
     "scopes": {"fusion.363": "jit(step)/.../lm_head/dot_general", ...},
     "opcodes": {"fusion.363": "fusion", "psum.433": "all-reduce", ...}}

The opcode is what an operation IS, whatever it is called: JAX names the
all-reduce of a bucket of one leaf ``psum.<n>``, which a reader that goes by
the name (``trace_reduce.is_collective``) takes for no collective.

Each instant of a launch's busy time goes to exactly ONE event, the innermost
one running then (a ``while`` event contains its body's events, and summing
durations would count the backward twice), so the groups of a step sum to that
step's busy time. Groups are data: ``scope_groups/<family>.json``, an ordered
list of rules, first match wins.
"""

from __future__ import annotations

import os
import re
import time

from . import manifest
from . import trace_reduce as tr

UNNAMED = "unnamed"
EXCHANGE = "hvd_exchange"
# In an HLO line (`%name = shape opcode(operands), ...`) the first lower-case
# word before a parenthesis: shapes and layouts hold none (T(8,128), S(1)).
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
# The stat of an event's metadata that holds the scope path (op_metadata).
_PATH_STAT = "tf_op"
# The scopes a parent of an exchange is named by (the step itself otherwise).
_PROGRAM_SCOPE = re.compile(r"hvd_[a-z_]+")


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped (none of the fields read here is one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield field, wire, value


def _map_value(entry):
    for field, _, value in _fields(entry):
        if field == 2:
            return value
    return b""


def op_metadata(path):
    """``({instruction name: scope path}, {instruction name: opcode})`` of
    the first TPU plane of an ``.xplane.pb``.

    Where the path is (looked at by hand on a v5e trace, PERF.md section 3):
    not on the event (its stats are two times and a multiplier) and not in
    the HLO line that the event's name holds (it is printed without
    ``metadata={...}``), but in the event's METADATA, whose ``tf_op`` stat is
    the HLO ``op_name`` with a trailing colon. ``jax.profiler.ProfileData``
    does not expose the metadata's stats, so the protobuf is read here by
    its wire format: ``XSpace.planes`` = 1; ``XPlane.name`` = 2,
    ``.event_metadata`` = 4, ``.stat_metadata`` = 5 (maps: key 1, value 2);
    ``XEventMetadata.name`` = 2, ``.stats`` = 5; ``XStat.metadata_id`` = 1,
    ``.str_value`` = 5, ``.ref_value`` = 7; ``XStatMetadata.name`` = 2. The
    lines (field 3, nearly all of the file) are skipped unread. The opcode
    is read from the HLO line that is the metadata's name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name = next((bytes(v).decode() for f, _, v in _fields(plane)
                     if f == 2), "")
        m = tr.DEVICE_PLANE.match(name)
        if m:
            planes.append((int(m.group(1)), plane))
    if not planes:
        return {}, {}
    plane = min(planes, key=lambda t: t[0])[1]
    stat_names, events = {}, []
    for field, wire, value in _fields(plane):
        if field == 5 and wire == 2:
            key = name = None
            for f, _, v in _fields(_map_value(value)):
                if f == 1:
                    key = v
                elif f == 2:
                    name = bytes(v).decode()
            stat_names[key] = name
        elif field == 4 and wire == 2:
            events.append(_map_value(value))
    wanted = {k for k, v in stat_names.items() if v == _PATH_STAT}
    paths, opcodes = {}, {}
    for event in events:
        name, found = "", ""
        for f, _, v in _fields(event):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5:
                sid = text = ref = None
                for sf, _, sv in _fields(v):
                    if sf == 1:
                        sid = sv
                    elif sf == 5:
                        text = bytes(sv).decode()
                    elif sf == 7:
                        ref = stat_names.get(sv, "")
                if sid in wanted:
                    found = text if text is not None else (ref or "")
        if name:
            short = tr.op_name(name)
            paths[short] = found.rstrip(":")
            m = _OPCODE.search(name.partition(" = ")[2])
            opcodes[short] = m.group(1) if m else ""
    return paths, opcodes


def scoped_trace(trace, paths, opcodes, match=None):
    """A plain trace (``trace_reduce.load_xplane``) and ``op_metadata`` -> a
    scoped trace: chip 0's modules and ops lines, and the scope path of
    every op that ran inside a launch that ``match`` selects. An op that
    carries no path of its own and contains others (XLA gives a ``while``
    none) takes the path of the first op that ran inside it: a container is
    named by its contents."""
    planes = tr.device_planes(trace)[:1]
    scopes = {}
    for plane in planes:
        for launch in tr.per_launch(plane, match, skip=0):
            ops = launch["ops"]
            for i, (name, start, dur) in enumerate(ops):
                if name in scopes:
                    continue
                scopes[name] = paths.get(name, "")
                j = i + 1
                while (not scopes[name] and j < len(ops)
                       and ops[j][1] < start + dur):
                    scopes[name] = paths.get(ops[j][0], "")
                    j += 1
    return {"planes": [
        {"name": p["name"], "lines": [l for l in p["lines"]
                                      if l["name"] in (tr.MODULES_LINE,
                                                       tr.OPS_LINE)]}
        for p in planes
    ], "scopes": scopes, "opcodes": {n: opcodes.get(n, "") for n in scopes}}


def load_scoped(path, match=None):
    """An ``.xplane.pb`` -> a scoped trace (how ``testdata/`` was recorded)."""
    return scoped_trace(tr.load_xplane(path), *op_metadata(path), match)


def innermost_ns(events, lo, hi):
    """``[ns]`` aligned with ``events`` (``[name, start, dur]``, sorted by
    start): the part of ``[lo, hi)`` during which each event was the
    innermost one running, that is the one that started last. The parts are
    disjoint and sum to the union of the events' intervals."""
    out = [0] * len(events)
    stack = []  # (end, index); the top started last
    cur = lo

    def advance(to):
        nonlocal cur
        while stack and cur < to:
            end, i = stack[-1]
            if end <= cur:
                stack.pop()
                continue
            upto = min(end, to)
            out[i] += upto - cur
            cur = upto
        cur = max(cur, to)

    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        start = max(events[i][1], lo)
        end = min(events[i][1] + events[i][2], hi)
        if end <= start:
            continue
        advance(start)
        stack.append((end, i))
    advance(hi)
    return out


class Groups:
    """``scope_groups/<family>.json``: the rules ``(group, path regex, op
    regex or None)`` in order, the names of all groups, and the scope a
    program's step must carry for the grouping to mean anything."""

    def __init__(self, family):
        doc = manifest.load_json(groups_file(family))
        self.rules = [(r["group"], re.compile(r["path"]),
                       re.compile(r["op"]) if r.get("op") else None)
                      for r in doc["rules"]]
        self.names = list(dict.fromkeys(
            [g for g, _, _ in self.rules] + [UNNAMED]
        ))
        self.program_scope = doc["program_scope"]


def groups_file(family):
    return os.path.join(manifest.HERE, "scope_groups", family + ".json")


def group_of(rules, opcode, path):
    """First rule whose ``path`` regex finds the scope path and whose ``op``
    regex (if it has one) finds the operation's opcode."""
    for group, path_re, op_re in rules:
        if path_re.search(path) and (op_re is None or op_re.search(opcode)):
            return group
    return UNNAMED


def exchange_parent(path):
    """What an ``hvd_exchange`` lies under: the nearest ``hvd_*`` scope
    above it, or ``step`` for the step's own exchange."""
    above = _PROGRAM_SCOPE.findall(path.split(EXCHANGE, 1)[0])
    return above[-1] if above else "step"


def classify(scoped, rules):
    """``{instruction name: (group, exchange parent or None, is it a
    collective)}`` for every op of a scoped trace."""
    out = {}
    for name, path in scoped["scopes"].items():
        opcode = scoped["opcodes"].get(name, "")
        out[name] = (group_of(rules, opcode, path),
                     exchange_parent(path) if EXCHANGE in path else None,
                     tr.is_collective(opcode))
    return out


def reduce_launch(launch, classes):
    """One launch -> ``(ns by group, {parent: [collective ns, copy ns]})``
    of the exchanges."""
    ops = launch["ops"]
    own = innermost_ns(ops, launch["start"], launch["end"])
    groups, parents = {}, {}
    for (name, _, _), ns in zip(ops, own):
        if not ns:
            continue
        group, parent, collective = classes.get(name, (UNNAMED, None, False))
        groups[group] = groups.get(group, 0) + ns
        if parent is not None:
            pair = parents.setdefault(parent, [0, 0])
            pair[0 if collective else 1] += ns
    return groups, parents


def reduce(scoped, groups, match=None):
    """A scoped trace -> what the ``scopes:`` line and the readers hold:
    per group the median milliseconds per step over the steady launches of
    chip 0, the median busy time, and the exchanges by parent. ``None``
    where there is nothing to read: no device plane, no launch, or a program
    whose step does not carry the scopes (the parent of the PR that brought
    them)."""
    planes = tr.device_planes(scoped)
    launches = tr.per_launch(planes[0], match) if planes else []
    if not launches or not any(groups.program_scope in p
                               for p in scoped["scopes"].values()):
        return None
    classes = classify(scoped, groups.rules)
    per = [reduce_launch(l, classes) for l in launches]
    ms = lambda values: tr.median(values) / 1e6
    by_group = {g: ms([p[0].get(g, 0) for p in per]) for g in groups.names}
    parents = {}
    for parent in sorted({k for _, ex in per for k in ex}):
        pairs = [ex.get(parent, [0, 0]) for _, ex in per]
        parents[parent] = {"collective_ms": ms([a for a, _ in pairs]),
                           "copy_ms": ms([b for _, b in pairs])}
    busy = [l["busy"] for l in launches]
    worst = max(abs(sum(p[0].values()) - b) / b for p, b in zip(per, busy))
    return {"steps": len(launches), "busy_ms": ms(busy),
            "groups_ms": by_group, "groups_sum_ms": sum(by_group.values()),
            "worst_step_sum_gap": worst, "hvd_exchange": parents}


def of_run(run):
    """The reduction of this run's window, loaded once and kept on ``run``
    (``None`` where there is nothing to read: no trace, no device plane, a
    family without a grouping)."""
    if hasattr(run, "_scope_reduction"):
        return run._scope_reduction
    run._scope_reduction = None
    family = run.cell.config["family"]
    if not run.trace or not os.path.exists(groups_file(family)):
        return None
    t0 = time.perf_counter()
    try:
        path = tr.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    match = run.launch_match()
    # run.device_trace is the window's trace as every reader sees it, read
    # once per run; only the metadata's paths are read from the file again
    scoped = scoped_trace(run.device_trace, *op_metadata(path), match)
    result = reduce(scoped, Groups(family), match)
    if result is not None:
        result["reader_s"] = time.perf_counter() - t0
    run._scope_reduction = result
    return result


def group_ms(run, group):
    """What a per-layer reader returns: one group's milliseconds per step."""
    result = of_run(run)
    return None if result is None else result["groups_ms"][group]
