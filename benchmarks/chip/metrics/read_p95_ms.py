"""read_p95_ms: 95th percentile time of all point retrievals in the
window (host clock, ms; linear interpolation)."""
import numpy as np


def read(run):
    lat = [r["latency_s"] for r in run["records"]]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
