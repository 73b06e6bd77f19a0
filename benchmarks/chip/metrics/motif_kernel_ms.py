"""motif_kernel_ms: device time of the ``temporal_motif`` kernel per
triangle query, averaged over the window's triangle queries (profiler
trace, ms)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    per = [v["kernels"].get("temporal_motif", 0.0)
           for k, v in t["per_span"].items() if k.split(":")[1] == "triangles"]
    if not per or not any(per):
        return None
    return 1e3 * sum(per) / len(per)
