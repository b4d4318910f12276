"""From a profiler trace to device metrics: the reduction kept with the
benchmark (copied in spirit from obs/attribution.py: named scopes ->
categories, union of intervals -> busy time), reading the profiler's
``.xplane.pb`` directly.

Two steps, so that the second can be tested on a small recorded trace:
``load(path)`` turns the protobuf into plain lists; ``reduce(rep, rules, L)``
turns those into busy time, per-category time, forward passes and the
breakdown the last line may carry.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: stats whose text carries the jax.named_scope path of a device op.
SCOPE_STATS = ("tf_op", "long_name", "hlo_op", "op_name", "deduplicated_name")
#: a gap shorter than this is not worth attributing to a host frame.
MIN_GAP_NS = 20_000


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _stat_text(stat, stat_names: Dict[int, str]) -> Optional[str]:
    """A stat's value if it is text (a string, or a reference to an interned
    one)."""
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value)
    return None


def load(path: str, max_events_per_line: Optional[int] = None,
         all_stats: bool = False) -> dict:
    """The trace as plain data: planes -> lines -> events
    ``[name, start_ns, duration_ns, scope_text]``. For a device op the scope
    text is every textual stat of the op's metadata (``tf_op`` carries the
    jax.named_scope path); ``all_stats`` keeps the stats' names too, for a
    look by hand."""
    from xplane_proto import parse

    planes = []
    for plane in parse(path).planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        is_device = plane.name.startswith("/device:")
        scope_of: Dict[int, str] = {}

        def scope(mid: int) -> str:
            if mid not in scope_of:
                parts = []
                md = meta.get(mid)
                for st in (md.stats if md is not None else ()):
                    text = _stat_text(st, stat_names)
                    key = stat_names.get(st.metadata_id, "?")
                    if all_stats:
                        val = text if text is not None else (
                            st.int64_value or st.uint64_value or st.double_value)
                        parts.append(f"{key}={str(val)[:200]}")
                    elif text is not None and key in SCOPE_STATS:
                        parts.append(text)
                scope_of[mid] = "; ".join(parts) if all_stats else " ".join(parts)
            return scope_of[mid]

        lines = []
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            events = []
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                name = (md.name or md.display_name) if md is not None else "?"
                events.append([name, (base + ev.offset_ps) // 1000, ev.duration_ps // 1000,
                               scope(ev.metadata_id) if is_device else ""])
                if max_events_per_line and len(events) >= max_events_per_line:
                    break
            lines.append({"name": line.name or line.display_name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def categorize(name: str, scope: str, rules: dict) -> str:
    n = name.lower()
    # an op that is itself a collective is billed to no scope it sits in: an
    # all-reduce inside ``mlp`` is the mesh's time, not the weight stream's
    for cat, keys in rules.get("op_rules", ()):
        if any(k in n for k in keys):
            return cat
    s = scope.lower()
    for cat, keys in rules["scope_rules"]:
        if any(k in s for k in keys):
            return cat
    for cat, keys in rules["hlo_rules"]:
        if any(k in n for k in keys):
            return cat
    return "other_device"


def op_name(text: str) -> str:
    """An op event is named by its whole HLO instruction,
    ``%fusion.123 = bf16[...] fusion(...)``: the name is ``fusion.123``."""
    return text.lstrip("%").split(" ", 1)[0]


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``copy-done.4`` -> ``copy-done``."""
    return re.sub(r"(\.(\d+|remat\d*|clone))+$", "", name) or name


def self_times(ops: List[list]) -> List[int]:
    """Events of one line nest (a ``while`` spans the ops of its body). An
    event's self time is its duration less that of its direct children, so
    that time is billed once, to the innermost op that was running."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e[2] for e in ops]
    stack: List[int] = []
    for i in order:
        start = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [max(0, t) for t in own]


def _label(text: str) -> str:
    return "host:" + re.sub(r"[^A-Za-z0-9_.:\-]", "_", text)[:80]


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _launch_thread(host_lines: List[dict]) -> Optional[dict]:
    """The Python thread that dispatches device programs: of the threads the
    Python tracer saw (frames are named ``$file:line function``), the one
    with the most frames from jax's dispatch path. Gaps on the device are
    explained by what IT was doing."""
    marks = ("pjit", "pxla", "jaxlib", "dispatch")
    best, best_n = None, 0
    for line in host_lines:
        n = sum(1 for e in line["events"]
                if e[0].startswith("$") and any(m in e[0] for m in marks))
        if n > best_n:
            best, best_n = line, n
    return best


def _deepest_at(events: List[list], t: int) -> Optional[str]:
    """The innermost source-level frame on a thread at time t (C builtins,
    which the tracer names ``$builtins ...`` or ``$<unknown> ...``, say
    nothing about where the time went and are passed over)."""
    best = None
    for name, s, d, _ in events:
        if name.startswith(("$builtins", "$<unknown>")):
            continue
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else None


def reduce(rep: dict, rules: dict, n_layers: int, chips: int = 1) -> dict:
    """Busy and window seconds (averaged over the device planes that ran
    anything), seconds per category, forward passes, and the breakdown.
    ``problems`` says why the trace cannot stand for a cell of ``chips``
    chips: fewer device planes than chips, or planes that did not run the
    same passes. One program across the mesh runs every pass on every chip,
    in step through its collectives; each of the capture's two edges may cut
    a pass on some planes and not on others, so sound planes differ by up to
    2 (a model:4 capture read 220, 219, 218, 218; my chip run, PR 26)."""
    dev = [p for p in rep["planes"] if p["name"].startswith("/device:")
           and any(l["name"] == OPS_LINE and l["events"] for l in p["lines"])]
    host_lines = [l for p in rep["planes"] if p["name"].startswith("/host:")
                  for l in p["lines"]]
    if not dev:
        return {"devices": 0, "problems": ["the trace holds no device plane that ran an op"]}
    cat_ns: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, int] = defaultdict(int)
    gap_ns: Dict[str, int] = defaultdict(int)
    busy, window, passes = [], [], []
    launcher = _launch_thread(host_lines)
    for plane in dev:
        ops = next(l["events"] for l in plane["lines"] if l["name"] == OPS_LINE)
        merged = _merge([(s, s + d) for _, s, d, _ in ops if d > 0])
        lo, hi = merged[0][0], merged[-1][1]
        busy.append(sum(e - s for s, e in merged))
        window.append(hi - lo)
        head_counts: Dict[Tuple[int, str], int] = defaultdict(int)
        mods = sorted((s, s + d) for l in plane["lines"] if l["name"] == MODULES_LINE
                      for _, s, d, _ in l["events"])
        mi = 0
        own = self_times(ops)
        for (text, s, _, scope), d in sorted(zip(ops, own), key=lambda p: p[0][1]):
            name = op_name(text)
            cat = categorize(name, scope, rules)
            cat_ns[cat] += d
            op_ns[f"{cat}:{op_family(name)}"] += d
            if cat == "lm_head":
                while mi + 1 < len(mods) and mods[mi + 1][0] <= s:
                    mi += 1
                head_counts[(mi, name)] += 1
        per_module: Dict[int, int] = defaultdict(int)
        for (m, _), c in head_counts.items():
            per_module[m] = max(per_module[m], c)
        passes.append(sum(per_module.values()))
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gap = s1 - e0
            if gap < MIN_GAP_NS or launcher is None:
                gap_ns["short_gaps_unlabelled"] += gap
                continue
            what = _deepest_at(launcher["events"], (e0 + s1) // 2)
            gap_ns[_label(what) if what else "host:_unknown"] += gap
    n = len(dev)
    problems = []
    if n < chips:
        problems.append(f"the trace holds {n} device planes, the cell runs on {chips} chips")
    if max(passes) - min(passes) > 2:
        problems.append(f"the device planes ran different numbers of forward passes: {passes}")
    # a chip's seconds, like busy_s and category_s: the planes' sum over n
    top = lambda d: [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(window) / n / 1e9,
        "category_s": {k: v / n / 1e9 for k, v in cat_ns.items()},
        "forward_passes": sum(passes) / n,
        "n_layers": n_layers,
        "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(gap_ns)},
        "problems": problems,
    }


def excerpt(rep: dict, start_s: float, length_s: float) -> dict:
    """A small recorded trace for the tests: the events that lie wholly inside
    ``length_s`` seconds starting ``start_s`` after the first device op, with
    long HLO texts cut (the op's name comes first and survives)."""
    t0 = min(e[1] for p in rep["planes"] if p["name"].startswith("/device:")
             for l in p["lines"] for e in l["events"]) + int(start_s * 1e9)
    t1 = t0 + int(length_s * 1e9)
    planes = []
    for p in rep["planes"]:
        lines = [{"name": l["name"],
                  "events": [[e[0][:90], e[1] - t0, e[2], e[3][:160]] for e in l["events"]
                             if t0 <= e[1] and e[1] + e[2] <= t1]}
                 for l in p["lines"]]
        lines = [l for l in lines if l["events"]]
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def describe(rep: dict, per_line: int = 6) -> str:
    """A look at a trace by hand: planes, lines, a few events of each."""
    out = []
    for p in rep["planes"]:
        out.append(f"PLANE {p['name']!r}: {len(p['lines'])} lines")
        for l in p["lines"]:
            ev = l["events"]
            out.append(f"  LINE {l['name']!r}: {len(ev)} events")
            for e in ev[:per_line]:
                out.append(f"    {json.dumps(e)[:400]}")
    return "\n".join(out)
