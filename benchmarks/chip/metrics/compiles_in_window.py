"""compiles_in_window: programs traced by the store's plan compiler
during the window (delta of ``repro.taf.compile.STATS["traces"]``)."""


def read(run):
    return run["stats_after"]["traces"] - run["stats_before"]["traces"]
