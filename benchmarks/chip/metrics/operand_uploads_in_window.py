"""operand_uploads_in_window: device operand exports built during the
window (delta of ``repro.taf.compile.STATS["operand_uploads"]``)."""


def read(run):
    return (run["stats_after"]["operand_uploads"]
            - run["stats_before"]["operand_uploads"])
