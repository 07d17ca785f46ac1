"""Device: the share of the traced window in which no operation ran on
the chip (1 - the union of device-op intervals over the window), mean
over the cell's chips."""


def read(rec):
    tr = rec.trace
    if not tr or not tr.get("busy_frac"):
        return None
    return 100.0 * (1.0 - sum(tr["busy_frac"]) / len(tr["busy_frac"]))
