"""node_slot_fill: of the slots of the padded node-event tables that the
node-operand exports built (N x Emax each), the share that hold a real
event, in percent (from ``repro.taf.compile.STATS["node_events"]`` and
``["node_slots"]`` after the window).  The node-presence search reads
every slot, so a low fill is work spent on padding.  Nothing where the
program has no such counters or built no node operand."""


def read(run):
    stats = run["stats_after"]
    if not stats.get("node_slots") or "node_events" not in stats:
        return None
    return 100 * stats["node_events"] / stats["node_slots"]
