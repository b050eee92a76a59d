"""``device_idle_pct``: 1 - (union of the intervals in which an operation
ran on the device) / traced window, averaged over the cell's chips."""


def read(run):
    if not run.reduced:
        return {}
    return {"device_idle_pct": 100.0 * run.reduced["idle_share"]}
