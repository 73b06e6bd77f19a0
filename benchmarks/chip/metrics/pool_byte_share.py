"""pool_byte_share: raw bytes served from the decoded-block pool over
all raw bytes the window's reads touched (``FetchCost.n_bytes_pool``
over it plus ``n_bytes_decompressed``, summed), in %."""


def read(run):
    costs = [r["cost"] for r in run["records"] if r["cost"]]
    total = sum(c["raw"] + c["pool"] for c in costs)
    if not total:
        return None
    return 100.0 * sum(c["pool"] for c in costs) / total
