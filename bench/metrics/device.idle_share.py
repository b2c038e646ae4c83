"""device.idle_share: percent of the traced window in which no op ran on a
chip (1 - busy / window, busy the union of the chip's op intervals), the
mean over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace["per_device"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
