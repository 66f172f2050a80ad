"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of the device's op intervals) / window."""


def read(run):
    if run["driver"] != "serve" or run["trace"] is None:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
