"""pagerank_roofline: least time of the sampled PageRank queries (from
their shapes, ``chipbench/counts.py``, against the chip's peaks) over
the device time the trace shows inside their spans, in %."""


def read(run):
    t, least = run["trace"], run["least"].get("pagerank")
    if t is None or not least:
        return None
    dev = lower = 0.0
    for i, c in least.items():
        d = t["per_span"].get(f"req:pagerank:{i}", {}).get("device_s", 0.0)
        if d > 0:
            dev += d
            lower += c["least_s"]
    return 100.0 * lower / dev if dev else None
