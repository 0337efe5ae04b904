"""device_idle_share (%): the share of the traced window in which no
operation ran on the device (1 - union of operation intervals / window,
averaged over the chips used)."""


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
