"""Reduction of a profiler trace to device busy time, idle gaps and
device time per request.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form: per device, the intervals of its operations; and the host
spans that the harness opens around each request
(``req:<op>:<index>``).  ``reduce`` works on that form alone, so a test
can hand it intervals whose busy and idle times are known.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

import numpy as np

SPAN_PREFIX = "req:"
KERNELS = ("temporal_motif", "delta_overlay_batch", "delta_overlay")
OP_LINE = "XLA Ops"  # a TPU plane's line of device operations


def _kernel_of(name: str, stats: dict) -> str:
    text = " ".join([name] + [str(v) for v in stats.values()])
    for k in KERNELS:  # longest names first: delta_overlay_batch
        if k in text:
            return k
    return ""


def extract(trace_dir: str) -> dict:
    """{"devices": {plane: [(start_ns, end_ns, name, kernel)]},
    "spans": [(start_ns, end_ns, name)]} from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, List[Tuple[float, float, str, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    try:
                        stats = dict(ev.stats)
                    except (TypeError, ValueError):
                        stats = {}
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, _kernel_of(ev.name, stats)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
    return {"devices": devices, "spans": sorted(spans)}


def short_name(hlo: str) -> str:
    """``%while.14 = (s32[46744,64], ...) while(...)`` -> the op's name
    and the start of its result type, at most 96 characters."""
    head, _, rest = hlo.partition(" = ")
    return (head + " " + rest.split(" ")[0])[:96] if rest else hlo[:96]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _span_label(spans, t: float) -> str:
    for s, e, name in spans:
        if s <= t < e:
            return name.split(":")[1]
    return "between requests"


def reduce(ex: dict, window: Tuple[float, float]) -> dict:
    """Busy and idle time over ``window`` (ns, on the trace's clock),
    device time and kernel time per request span, the device ops that
    took most time, and the longest idle gaps labelled by what the host
    was doing in them."""
    w0, w1 = window
    win_s = (w1 - w0) / 1e9
    busy, op_time, gaps = [], {}, []
    per_span = {name: {"device_s": 0.0, "kernels": {}}
                for _, _, name in ex["spans"]}
    starts = np.asarray([s for s, _, _ in ex["spans"]], np.float64)
    for ops in ex["devices"].values():
        iv = np.asarray([(max(s, w0), min(e, w1)) for s, e, _, _ in ops
                         if e > w0 and s < w1], np.float64).reshape(-1, 2)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) / 1e9 if len(u) else 0.0)
        edges = np.r_[w0, u.ravel(), w1].reshape(-1, 2) if len(u) else \
            np.asarray([[w0, w1]])
        for s, e in edges:
            if e > s:
                gaps.append((float(e - s) / 1e9,
                             _span_label(ex["spans"], (s + e) / 2)))
        in_span = {}
        for s, e, name, kernel in ops:
            if e <= w0 or s >= w1:
                continue
            d = (e - s) / 1e9
            key = kernel or short_name(name)
            op_time[key] = op_time.get(key, 0.0) + d
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and s < ex["spans"][i][1]:
                in_span.setdefault(i, []).append((s, e))
                if kernel:
                    rec = per_span[ex["spans"][i][2]]["kernels"]
                    rec[kernel] = rec.get(kernel, 0.0) + d
        # an op nested in another (a loop and its body) counts once
        for i, iv in in_span.items():
            u = _union(np.asarray(iv, np.float64))
            per_span[ex["spans"][i][2]]["device_s"] += float(
                (u[:, 1] - u[:, 0]).sum()) / 1e9
    n_dev = max(len(ex["devices"]), 1)
    busy_s = sum(busy) / n_dev
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:10]
    return {
        "window_s": win_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / win_s if win_s > 0 else None,
        "per_span": per_span,
        "breakdown": {"device_ops": [[k, v / n_dev] for k, v in top_ops],
                      "idle_gaps": [[lbl, d] for d, lbl in top_gaps]},
    }
