"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-program device time.

``jax.profiler`` writes an ``.xplane.pb`` file; ``ProfileData`` reads it.
A TPU's operations are the events of the ``XLA Ops`` line of its
``/device:TPU:N`` plane, and the programs they belong to are the events
of its ``XLA Modules`` line. The CPU backend has no device plane: it
runs each operation on a host thread, as an event that carries an
``hlo_op`` statistic (and its program in ``hlo_module``), so the same
reduction reads a CPU trace too. The benchmark's own marks
(``bench.*`` trace annotations) come from the host plane. All times are
nanoseconds on the trace's one timeline.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench."


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def short_name(name: str, module: str | None) -> str:
    """``program:op`` without instance numbers: ``%fusion.12 = (...)
    fusion(...)`` in ``jit_build_cascade(123)`` is
    ``build_cascade:fusion``."""
    op = re.sub(r"(\.(\d+|clone))+$", "", name.split(" = ")[0].lstrip("%"))
    if not module:
        return op
    program = re.sub(r"\(\d+\)$", "", module)
    return f"{program.removeprefix('jit_')}:{op}"


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def read(path: str) -> dict:
    """-> {"ops": [{device, name, module, start, end}], "marks":
    [{name, start, end}]} from one ``.xplane.pb`` file; an operation's
    ``name`` is its :func:`short_name`."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, marks = [], []
    host_ops = []
    for plane in data.planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get(MODULES_LINE, ()))
            starts = [m[0] for m in modules]
            for e in lines[OPS_LINE]:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = (modules[i][2] if i >= 0 and e.start_ns < modules[i][1]
                          else None)
                ops.append({"device": plane.name,
                            "name": short_name(e.name, module),
                            "module": module, "start": e.start_ns,
                            "end": e.start_ns + e.duration_ns})
        elif plane.name.startswith("/host:"):
            for events in lines.values():
                for e in events:
                    if e.name.startswith(MARK_PREFIX):
                        marks.append({"name": e.name, "start": e.start_ns,
                                      "end": e.start_ns + e.duration_ns})
                        continue
                    st = _stats(e)
                    if "hlo_op" in st:
                        module = st.get("hlo_module")
                        host_ops.append({"device": plane.name,
                                         "name": short_name(e.name, module),
                                         "module": module,
                                         "start": e.start_ns,
                                         "end": e.start_ns + e.duration_ns})
    return {"ops": ops or host_ops, "marks": marks}


def clip(ops: list[dict], start: float, end: float) -> list[dict]:
    """The operations' parts that lie in [start, end)."""
    out = []
    for op in ops:
        s, e = max(op["start"], start), min(op["end"], end)
        if e > s:
            out.append({**op, "start": s, "end": e})
    return out


def busy_intervals(ops: list[dict]) -> list[tuple]:
    """Union of the operations' intervals, merged and sorted."""
    merged = []
    for s, e in sorted((op["start"], op["end"]) for op in ops):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def busy_ns(ops: list[dict]) -> float:
    """Busy time averaged over the devices that ran any operation."""
    per_device = defaultdict(list)
    for op in ops:
        per_device[op["device"]].append(op)
    if not per_device:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(d))
               for d in per_device.values()) / len(per_device)


def idle_gaps(ops: list[dict], start: float, end: float) -> list[tuple]:
    """Intervals of [start, end) in which no operation ran on any
    device, longest first."""
    gaps, t = [], start
    for s, e in busy_intervals(ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if end > t:
        gaps.append((t, end))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def outermost(ops: list[dict]) -> list[dict]:
    """The operations that no other operation of their device encloses:
    a ``conditional`` or ``while`` is kept, the operations it ran inside
    are not, so their time is counted once."""
    out, end = [], {}
    for op in sorted(ops, key=lambda o: (o["device"], o["start"], -o["end"])):
        if op["end"] <= end.get(op["device"], float("-inf")):
            continue
        out.append(op)
        end[op["device"]] = op["end"]
    return out


def totals(ops: list[dict], key: str = "name") -> dict:
    """{name (or module): summed duration in ns}."""
    out = defaultdict(float)
    for op in ops:
        out[op[key]] += op["end"] - op["start"]
    return dict(out)


def module_time_ns(ops: list[dict], fragment: str) -> float:
    """Device time in which an operation of a program whose name holds
    ``fragment`` ran, averaged over devices."""
    return busy_ns([op for op in ops
                    if op["module"] and fragment in op["module"]])
