"""From a profiler trace (``.xplane.pb``) to busy and idle seconds,
time per operation and per compiled program, and the longest idle gaps
with what the host was doing in them. Reads with
``jax.profiler.ProfileData`` only.

A trace is planes (one per device, one per host), each with lines (a
device's "XLA Ops", "XLA Modules", "Steps"; a host's threads), each
with events (name, start and duration in nanoseconds).
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[Dict[str, Any]]:
    """The trace as plain data: [{"plane", "line", "events": [(name,
    start_s, duration_s)]}]."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.append({"plane": plane.name, "line": line.name,
                        "events": [(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                                   for e in line.events]})
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def device_planes(lines: List[Dict[str, Any]]) -> List[str]:
    return sorted({l["plane"] for l in lines
                   if l["plane"].startswith("/device:")
                   and l["line"] == OPS_LINE})


def host_as_device(lines: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Tests on the CPU only: the host's XLA threads stand in for a
    device plane (one "chip"), and the ``PjitFunction`` host events for
    its modules, so that a traced run can be driven end to end where
    there is no device. Never used on a run that must hold a TPU."""
    ops, mods = [], []
    for l in lines:
        if l["line"].startswith("tf_XLA"):
            ops += [e for e in l["events"] if "ThunkExecutor" not in e[0]]
        mods += [("jit_" + n[len("PjitFunction(jit("):].rstrip(")"), s, d)
                 for n, s, d in l["events"]
                 if n.startswith("PjitFunction(jit(")]
    return lines + [
        {"plane": "/device:host-stand-in", "line": OPS_LINE, "events": ops},
        {"plane": "/device:host-stand-in", "line": MODULES_LINE,
         "events": mods}]


def _line(lines, plane: str, name: str) -> List[Tuple[str, float, float]]:
    for l in lines:
        if l["plane"] == plane and l["line"] == name:
            return l["events"]
    return []


def _host_events(lines) -> List[Tuple[str, float, float]]:
    ev = []
    for l in lines:
        if l["plane"].startswith("/host:"):
            ev.extend(l["events"])
    return ev


# Host frames that only wait: a gap is owned by what ran, not by who
# slept (the Python tracer names frames "$file:line function").
WAITING = ("$threading.py", "$queue.py", "$selectors.py", "$socketserver.py",
           "$<unknown>", "$time ", "$sys ", "$builtins ")


def _gap_owner(host, mid: float) -> str:
    """The shortest host event that spans the gap's middle and is not
    a wait. The program annotates nothing yet, so this is a Python
    frame or a runtime event (PERF.md, for the tracing issue)."""
    best: Optional[Tuple[float, str]] = None
    for name, start, dur in host:
        if start <= mid <= start + dur and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "no host span (not annotated)"


def short_op(name: str, limit: int = 96) -> str:
    """"%fusion.1 = f32[8]{0} fusion(...)" -> "%fusion.1 f32[8] fusion"."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:limit]
    if rest.startswith("("):            # a tuple: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, tail = "(...)", rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    opcode = tail.split("(", 1)[0].strip()
    return f"{head} {shape} {opcode}".strip()[:limit]


def is_control_flow(name: str) -> bool:
    """Ops that only enclose others (their time is their children's)."""
    return any(f" {op}(" in name for op in ("while", "conditional", "call"))


def reduce(lines: List[Dict[str, Any]], collective_markers=(
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute")) -> Dict[str, Any]:
    """Busy/idle per device (averaged), per-op and per-program sums on
    the first device, exposed collective time, top ops and idle gaps."""
    planes = device_planes(lines)
    if not planes:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line: nothing ran on the device")
    busy_each, window_each = [], []
    for p in planes:
        ops = _line(lines, p, OPS_LINE)
        spans = [(s, s + d) for _, s, d in ops]
        busy_each.append(total(union(spans)))
        window_each.append(max(b for _, b in spans) - min(a for a, _ in spans)
                           if spans else 0.0)
    first = planes[0]
    ops = _line(lines, first, OPS_LINE)
    by_op: Dict[str, List[float]] = {}
    for name, _, d in ops:
        by_op.setdefault(name, []).append(d)
    by_module: Dict[str, List[float]] = {}
    for name, _, d in _line(lines, first, MODULES_LINE):
        by_module.setdefault(name, []).append(d)
    def is_coll(name: str) -> bool:
        """By the op's own name and opcode, or the collective a custom
        fusion calls: never by its operands' names."""
        head, _, rest = name.partition(" = ")
        own = head + " " + short_op(name).rsplit(" ", 1)[-1]
        called = rest.split("calls=", 1)[1] if "calls=" in rest else ""
        return any(m in own or m in called for m in collective_markers)

    coll = union((s, s + d) for n, s, d in ops if is_coll(n))
    comp = union((s, s + d) for n, s, d in ops
                 if not is_coll(n) and not is_control_flow(n))
    busy = union((s, s + d) for _, s, d in ops)
    host = [e for e in _host_events(lines) if not e[0].startswith(WAITING)]
    gaps = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                   for a, b in zip(busy, busy[1:])), reverse=True)
    owners: Dict[str, float] = {}
    for length, mid in gaps[:50]:
        owner = _gap_owner(host, mid)
        owners[owner] = owners.get(owner, 0.0) + length
    top = lambda d, n=10: sorted(
        ((short_op(k), sum(v)) for k, v in d.items()
         if not is_control_flow(k)), key=lambda kv: -kv[1])[:n]
    return {
        "devices": len(planes),
        "busy_s": sum(busy_each) / len(planes),
        "window_s": max(window_each),
        "op_seconds": {k: sum(v) for k, v in by_op.items()},
        "op_counts": {k: len(v) for k, v in by_op.items()},
        "module_durations": by_module,
        "collective_s": total(coll),
        "collective_exposed_s": total(subtract(coll, comp)),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top(by_op)],
            "idle_gaps": [[k, v] for k, v in sorted(
                owners.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def reduce_dir(trace_dir: str, host_fallback: bool = False
               ) -> Dict[str, Any]:
    lines = load(find_xplane(trace_dir))
    if host_fallback and not device_planes(lines):
        lines = host_as_device(lines)
    return reduce(lines)
