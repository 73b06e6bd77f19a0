"""device_idle_share: 1 - (union of device-op intervals) / (traced
window), averaged over the chips used, from the profiler trace, in %."""


def read(run):
    t = run["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * t["idle_share"]
