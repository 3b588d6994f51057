"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced window), averaged over
the cell's chips."""


def read(run):
    tr = run.window.trace
    if tr is None:
        return None
    s = tr["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
