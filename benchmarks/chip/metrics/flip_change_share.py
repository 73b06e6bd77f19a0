"""flip_change_share: of the pair events the edge-operand exports saw,
the share they kept as existence changes, in percent (from
``repro.taf.compile.STATS["flip_changes"]`` and ``["flip_events"]``
after the window).  Nothing where the program has no such counters."""


def read(run):
    stats = run["stats_after"]
    if not stats.get("flip_events") or "flip_changes" not in stats:
        return None
    return 100 * stats["flip_changes"] / stats["flip_events"]
