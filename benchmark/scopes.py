"""Device time by named scope: which stage of a program the device ran.

The program names its stages with ``jax.named_scope``; the cascade's are
``cascade.keys``, ``cascade.sort``, ``cascade.level{l}`` with its
``cascade.segment-reduce``, and the segment kernel's two ways through a
slab, ``slab.channel`` and ``slab.fallback``. The scope path rides on
every compiled operation as the ``op_name`` of its metadata:
``jit(build_cascade)/cascade.level3/cascade.segment-reduce/cond``.
Operations inside a called computation (a ``conditional``'s branches)
may carry a path relative to their caller,
``cond/branch_0_fun/slab.fallback/scatter-add``; such an operation takes
the scopes of the operation that encloses it in time.

Each instant of a device's busy time goes to the innermost operation
running then, and that time to the operation's scopes: an enclosing
operation keeps what its inner operations leave, and the scopes' times
add up to the busy time.

A trace event names its operation but carries no op_name: on a v5e an
``XLA Ops`` event's name is the instruction's HLO text without its
metadata, and its statistics are times alone; on the CPU an event
carries the instruction name (``hlo_op``). So the op_name comes from the
program's compiled HLO text, through the instruction name
(:func:`hlo_op_names`).

No metric reads this split yet: ``backfill.py`` would have to keep the
cascade's compiled HLO text and reduce the trace before its work
directory goes (PERF.md, section 7).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from benchmark.devtrace import MODULES_LINE, OPS_LINE, _stats

#: A named scope: dotted lower-case words (``cascade.level3``); JAX's
#: own path components (``jit(f)``, ``cond``, ``branch_0_fun``) have no
#: dot.
SCOPE = re.compile(r"[a-z][\w-]*(?:\.[\w-]+)+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def instruction(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ")[0].strip().lstrip("%")


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} from a compiled module's HLO text. An
    instruction without metadata that calls a computation (a fusion the
    compiler made) takes the op_name of that computation's root, or
    else of the first of its instructions that has one."""
    own, calls, roots, first = {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name = m.group(2)
        if m.group(1):
            roots[computation] = name
        op_name = _OP_NAME.search(line)
        if op_name:
            own[name] = op_name.group(1)
            first.setdefault(computation, op_name.group(1))
        else:
            called = _HLO_CALLS.search(line)
            if called:
                calls[name] = called.group(1)

    def resolve(name, depth=0):
        if name in own or depth > 8:
            return own.get(name)
        called = calls.get(name)
        root = roots.get(called)
        return (root and resolve(root, depth + 1)) or first.get(called)

    out = {}
    for name in {*own, *calls}:
        op_name = resolve(name)
        if op_name is not None:
            out[name] = op_name
    return out


def rooted(op_name: str) -> bool:
    """True when the path starts at the program (``jit(f)/...``)."""
    head = op_name.split("/", 1)[0]
    return head.endswith(")") and "jit(" in head


def scopes(op_name: str | None) -> tuple:
    """The named scopes on an op_name's path, outermost first."""
    if not op_name:
        return ()
    return tuple(p for p in op_name.split("/")[:-1] if SCOPE.fullmatch(p))


def read_ops(path: str, hlo_text: str, module: str) -> list[dict]:
    """-> [{device, op, module, start, end, op_name}] from one
    ``.xplane.pb`` file. ``hlo_text``, the compiled HLO of the program
    whose name holds ``module``, gives that program's operations their
    op_name; the others have None."""
    import jax

    names = hlo_op_names(hlo_text)
    data = jax.profiler.ProfileData.from_file(path)
    ops, host_ops = [], []

    def add(out, device, event, op, prog):
        op_name = names.get(op) if prog and module in prog else None
        out.append({"device": device, "op": op, "module": prog,
                    "start": event.start_ns,
                    "end": event.start_ns + event.duration_ns,
                    "op_name": op_name})

    for plane in data.planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            programs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in lines.get(MODULES_LINE, ()))
            starts = [p[0] for p in programs]
            for e in lines[OPS_LINE]:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                prog = (programs[i][2] if i >= 0 and e.start_ns < programs[i][1]
                        else None)
                add(ops, plane.name, e, instruction(e.name), prog)
        elif plane.name.startswith("/host:"):
            for events in lines.values():
                for e in events:
                    st = _stats(e)
                    if "hlo_op" in st:
                        add(host_ops, plane.name, e, str(st["hlo_op"]),
                            st.get("hlo_module"))
    return ops or host_ops


def innermost(ops: list[dict]) -> list[tuple]:
    """-> [(op, its scopes, ns in which it was the innermost operation
    running on its device)]. An operation without a path of its own from
    the program's root takes the scopes of the operation enclosing it."""
    out = []
    by_device = defaultdict(list)
    for op in ops:
        by_device[op["device"]].append(op)
    for device_ops in by_device.values():
        stack = []  # [op, scopes, ns], outermost first
        t = float("-inf")
        for op in sorted(device_ops, key=lambda o: (o["start"], -o["end"])):
            t = _close(stack, t, op["start"], out)
            if stack and op["start"] > t:
                stack[-1][2] += op["start"] - t
            t = max(t, op["start"])
            own = scopes(op["op_name"])
            if stack and not (op["op_name"] and rooted(op["op_name"])):
                own = stack[-1][1] + own
            stack.append([op, own, 0])
        _close(stack, t, float("inf"), out)
    return out


def _close(stack: list, t: float, limit: float, out: list) -> float:
    """Pop the operations that end by ``limit``, each credited with the
    time from ``t`` to its end; returns the new ``t``."""
    while stack and stack[-1][0]["end"] <= limit:
        entry = stack.pop()
        end = entry[0]["end"]
        if end > t:
            entry[2] += end - t
            t = end
        out.append(tuple(entry))
    return t


def scope_ns(ops: list[dict]) -> dict:
    """{scope path ("a/b", or "" for none): ns}, averaged over the
    devices that ran any operation; the values add up to
    ``devtrace.busy_ns(ops)``."""
    out = defaultdict(float)
    devices = {op["device"] for op in ops}
    for _, path, ns in innermost(ops):
        out["/".join(path)] += ns
    return {k: v / len(devices) for k, v in out.items()} if devices else {}


def under(scope_s: dict, scope: str, exclude: str | None = None) -> float:
    """Summed time of the paths that hold the scope ``scope`` and not
    ``exclude``."""
    return sum(v for k, v in scope_s.items()
               if scope in k.split("/")
               and not (exclude and exclude in k.split("/")))


def levels(scope_s: dict) -> dict:
    """{level: {"sort", "reduce", "fallback", "other"}} from
    ``cascade.level{l}``; time outside every level is under ``None``."""
    out = defaultdict(lambda: defaultdict(float))
    for path, v in scope_s.items():
        parts = path.split("/") if path else []
        lvl = next((int(p[len("cascade.level"):]) for p in parts
                    if re.fullmatch(r"cascade\.level\d+", p)), None)
        if "slab.fallback" in parts:
            kind = "fallback"
        elif "cascade.sort" in parts:
            kind = "sort"
        elif "cascade.segment-reduce" in parts:
            kind = "reduce"
        else:
            kind = "other"
        out[lvl][kind] += v
    return {k: dict(v) for k, v in out.items()}
