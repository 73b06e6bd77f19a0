"""analytics_queries_per_s: analytics queries answered over the whole
window, divided by the window's length (host clock)."""


def read(run):
    done = sum(r["ok"] for r in run["records"])
    return done / run["window_s"] if run["records"] else None
