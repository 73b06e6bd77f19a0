"""decoded_bytes_per_read: raw bytes physically decoded per read
(``FetchCost.n_bytes_decompressed``, mean over the window's reads)."""


def read(run):
    costs = [r["cost"] for r in run["records"] if r["cost"]]
    if not costs:
        return None
    return sum(c["raw"] for c in costs) / len(costs)
