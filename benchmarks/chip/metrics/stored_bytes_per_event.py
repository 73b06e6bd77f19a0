"""stored_bytes_per_event: encoded bytes the store keeps
(``storage_report()["totals"]["encoded"]``) per event ingested.  The
build writes every one of them, so it bears on the set-up time."""


def read(run):
    return run["stored_bytes"] / run["n_events"]
