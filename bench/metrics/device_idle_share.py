"""Share (%) of the traced window in which the device ran no operation:
1 - (union of device operation intervals) / window, averaged over the
devices."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.window
    return 100.0 * (1.0 - run.trace.busy_ns([(lo, hi)]) / (hi - lo))
