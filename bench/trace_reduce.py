"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`extract` reads it with ``jax.profiler.ProfileData`` into plain
event lists: the device's operations and program executions, and the
harness's host spans (``bench.*`` trace annotations). Both are on the
trace's one clock, in nanoseconds. :func:`reduce` turns those lists into
a :class:`Summary` for one window.

Kernels are recognised by the names the trace shows for them: the
jitted wrapper of each Pallas kernel appears in the operation's name
stack (``KERNELS``), and the wrapper's own small operations count with
its kernel. The tests check the reduction on hand-made events and on
one serve step recorded on a TPU v5e (``bench/testdata``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Optional

# kernel -> substrings of an operation's name or metadata that mark it
KERNELS = {
    "ecc_qmatmul": ("ecc_qmatmul",),
    "paged_attention": ("fused_page_attention", "chunked_page_attention"),
    "ecc_decode": ("ecc_decode",),
}
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Events:
    ops: list          # (name, start_ns, end_ns, kernel | None)
    modules: list      # (name, start_ns, end_ns)
    spans: list        # (name, start_ns, end_ns)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def classify(name: str, meta: str) -> Optional[str]:
    text = f"{name} {meta}"
    for kernel, marks in KERNELS.items():
        if any(m in text for m in marks):
            return kernel
    return None


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:        # events without stats
        return {}


def extract(path: str) -> Events:
    """Device operations, program executions and harness spans of a trace
    (``path`` is an .xplane.pb or a directory holding one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        # the first chip only: every cell of this benchmark is one chip
        if re.match(r"/device:TPU:0(\D|$)", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        st = _stats(e)
                        meta = " ".join(str(v) for v in st.values()
                                        if isinstance(v, str))
                        ops.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    classify(e.name, meta)))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Events(ops, modules, spans)


def save_events(ev: Events, path: str):
    """Write the extracted events as gzipped JSON (test data)."""
    import gzip
    import json
    with gzip.open(path, "wt") as f:
        json.dump({"ops": ev.ops, "modules": ev.modules, "spans": ev.spans},
                  f)


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: float
    kernel_ns: dict          # kernel -> device ns inside counted steps
    op_ns: dict              # operation group -> device ns in the window
    gaps: list               # (host span, ns), longest first
    steps: list              # (start_ns, end_ns, busy_ns) serve steps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


# operations that hold others (a scan's loop); their time is their body's
CONTAINERS = ("while", "conditional", "call")


def op_group(name: str, kernel: Optional[str]) -> Optional[str]:
    """The group an operation's time is counted under: its kernel, else
    its HLO instruction name without the number, with the result's shape
    where that is one array (``copy u8[800,16,20,128]``). ``None`` for a
    container, whose time its body's operations already count."""
    if kernel:
        return kernel
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)*(?:\s*=\s*(\S+))?(?:\s|$)", name)
    if not m:
        return name
    base, shape = m.group(1), m.group(2)
    if base in CONTAINERS:
        return None
    if shape and not shape.startswith("("):
        return f"{base} {re.sub(r'{.*', '', shape)}"
    return base


def _innermost(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "outside harness spans"


def reduce(ev: Events, step_module: str = "serve_step") -> Summary:
    """Busy time, kernel time and idle gaps inside the ``bench.window``
    span. Serve steps are the executions inside the window of the program
    whose name holds ``step_module``; kernel time counts only operations
    inside those executions."""
    win = [(s, e) for n, s, e in ev.spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("trace has no bench.window span")
    lo, hi = win[0]
    busy = clip(union((s, e) for _, s, e, _ in ev.ops), lo, hi)
    op_ns: dict = defaultdict(float)
    for name, s, e, k in ev.ops:
        group = op_group(name, k)
        if group and e > lo and s < hi:
            op_ns[group] += min(e, hi) - max(s, lo)
    gaps = []
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((_innermost(ev.spans, (prev + s) / 2), s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    execs = sorted((s, e) for n, s, e in ev.modules
                   if step_module in n and s >= lo and e <= hi)
    kernel_ns: dict = defaultdict(float)
    steps = []
    ops = sorted(ev.ops, key=lambda o: o[1])
    j = 0
    for s, e in execs:
        while j < len(ops) and ops[j][1] < s:
            j += 1
        inside = []
        k = j
        while k < len(ops) and ops[k][1] < e:
            inside.append(ops[k])
            k += 1
        for _, os_, oe, kern in inside:
            if kern:
                kernel_ns[kern] += min(oe, e) - os_
        steps.append((s, e, total(union((o[1], min(o[2], e))
                                        for o in inside))))
    return Summary(window_ns=float(hi - lo), busy_ns=total(busy),
                   kernel_ns=dict(kernel_ns), op_ns=dict(op_ns), gaps=gaps,
                   steps=steps)


def describe(path: str, limit: int = 40) -> str:
    """A by-hand look at a trace: planes, lines, and the heaviest device
    operations with their metadata."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: "
                   + ", ".join(f"{l.name!r}({len(list(l.events))})"
                               for l in lines))
        if plane.name.startswith("/device:TPU:0"):
            for l in lines:
                agg: dict = defaultdict(lambda: [0, 0.0, None])
                for e in l.events:
                    a = agg[e.name]
                    a[0] += 1
                    a[1] += e.duration_ns
                    if a[2] is None:
                        a[2] = _stats(e)
                for name, (n, ns, st) in sorted(agg.items(),
                                                key=lambda x: -x[1][1])[:limit]:
                    out.append(f"  [{l.name}] {name} x{n} {ns / 1e6:.3f} ms "
                               f"{ {k: (v if not isinstance(v, str) else v[:300]) for k, v in (st or {}).items()} }")
    return "\n".join(out)
