"""Reduction of the store's own host spans (``repro.trace``) in a
profiler trace to self time per span name, requests per operation, span
coverage of the requests, and idle-gap labels down to a span.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form: the program's spans and the harness's request spans
(``req:<op>:<index>``), each with the host thread it ran on.  The other
functions work on that form alone, so a test can hand them spans whose
self times are known.  A span is the program's when its name is
``<module>.<what>`` with one of ``MODULES``; the profiler's own events
(the Python tracer's ``$file:line`` frames, the runtime's) are not.

Self time is a span's duration minus the time its direct children on
the same thread cover; over a tree of spans the self times add up to
the time of its root, so the layers' self times and the time no span
covers add up to the requests' time.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

REQ_PREFIX = "req:"
MODULES = ("query", "plan", "tgi", "kvstore", "serialize", "snapshot",
           "delta", "replay", "compile", "overlay")

Span = Tuple[float, float, str, object]  # (start_ns, end_ns, name, thread)


def is_program(name: str) -> bool:
    mod, dot, what = name.partition(".")
    return bool(dot and what) and mod in MODULES


def extract(trace_dir: str) -> dict:
    """{"program": [Span], "requests": [Span]} from the newest trace
    under ``trace_dir``; a thread is ``(plane name, line index)``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    program: List[Span] = []
    requests: List[Span] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                if name.startswith(REQ_PREFIX):
                    out = requests
                elif is_program(name):
                    out = program
                else:
                    continue
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns, name,
                            (plane.name, i)))
    return {"program": sorted(program), "requests": sorted(requests)}


def _by_thread(spans: List[Span]) -> Dict[object, List[Span]]:
    out: Dict[object, List[Span]] = {}
    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        out.setdefault(s[3], []).append(s)
    return out


def self_times(program: List[Span], window: Tuple[float, float]
               ) -> Dict[str, float]:
    """Seconds of self time per span name, clipped to ``window`` (ns).
    Spans of one thread nest, so a span's direct children are disjoint
    and the time they cover is the sum of their durations."""
    w0, w1 = window

    def clip(s, e):
        return max(0.0, min(e, w1) - max(s, w0))

    out: Dict[str, float] = {}
    for spans in _by_thread(program).values():
        stack: List[list] = []  # [end, name, clipped duration, children]

        def close(top):
            out[top[1]] = out.get(top[1], 0.0) + (top[2] - top[3]) / 1e9
            if stack:
                stack[-1][3] += top[2]

        for s, e, name, _ in spans:
            while stack and stack[-1][0] <= s:
                close(stack.pop())
            stack.append([e, name, clip(s, e), 0.0])
        while stack:
            close(stack.pop())
    return out


def coverage(program: List[Span], requests: List[Span]) -> Optional[float]:
    """Share of the time inside request spans that some program span on
    the request's thread covers; None without requests."""
    roots = {}  # per thread: the outermost spans, disjoint and in order
    for thread, sp in _by_thread(program).items():
        starts, ends = roots.setdefault(thread, ([], []))
        for s, e, _, _ in sp:
            if not ends or s >= ends[-1]:
                starts.append(s)
                ends.append(e)
    req_ns = cov_ns = 0.0
    for s, e, _, thread in requests:
        req_ns += e - s
        starts, ends = roots.get(thread, ([], []))
        for j in range(bisect.bisect_right(ends, s),
                       bisect.bisect_left(starts, e)):
            cov_ns += min(ends[j], e) - max(starts[j], s)
    return cov_ns / req_ns if req_ns else None


def labeler(ex: dict):
    """A function of a time ``t`` (ns) that says what the host was doing
    then: ``<op>/<innermost program span>`` inside a request where a
    program span of its thread is open, the operation alone where none
    is, ``between requests`` outside every request (the labels of
    ``tracing.reduce``)."""
    threads = {}
    for thread, sp in _by_thread(ex["program"]).items():
        parent, stack = [], []
        for i, (s, _, _, _) in enumerate(sp):
            while stack and sp[stack[-1]][1] <= s:
                stack.pop()
            parent.append(stack[-1] if stack else -1)
            stack.append(i)
        threads[thread] = ([x[0] for x in sp], sp, parent)
    reqs = ex["requests"]
    starts = [r[0] for r in reqs]

    def label(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= reqs[i][1]:
            return "between requests"
        op = reqs[i][2].split(":")[1]
        if reqs[i][3] in threads:
            st, sp, parent = threads[reqs[i][3]]
            j = bisect.bisect_right(st, t) - 1
            while j >= 0 and sp[j][1] <= t:  # closed: climb to its parent
                j = parent[j]
            if j >= 0:
                return f"{op}/{sp[j][2]}"
        return op

    return label


def reduce(ex: dict, window: Tuple[float, float]) -> dict:
    """Self seconds per span name over ``window`` (ns), requests per
    operation inside it, and the share of request time that program
    spans cover."""
    w0, w1 = window
    reqs = [r for r in ex["requests"] if r[0] >= w0 and r[1] <= w1]
    per_op: Dict[str, int] = {}
    for _, _, name, _ in reqs:
        op = name.split(":")[1]
        per_op[op] = per_op.get(op, 0) + 1
    return {"layers": self_times(ex["program"], window),
            "requests": per_op,
            "coverage": coverage(ex["program"], reqs)}
