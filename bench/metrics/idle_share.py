"""Device: share of the traced slice with no op running, averaged over
the chips."""


def read(run, trace):
    if trace is None or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
