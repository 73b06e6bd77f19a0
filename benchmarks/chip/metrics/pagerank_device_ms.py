"""pagerank_device_ms: device time of the operations that ran inside
each PageRank query's span, averaged over the window's PageRank
queries (profiler trace, ms)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    per = [v["device_s"] for k, v in t["per_span"].items()
           if k.split(":")[1] == "pagerank"]
    if not per or not any(per):
        return None
    return 1e3 * sum(per) / len(per)
