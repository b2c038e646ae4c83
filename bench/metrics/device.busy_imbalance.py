"""device.busy_imbalance: how much longer the busiest chip worked than the
chips' mean, in percent: (max over chips of busy time / their mean - 1) x
100, busy the union of each chip's op intervals in the traced window
(``devtrace.reduce``'s ``per_device``). Chip 0, which holds the tables
before each call spreads them, is the one to watch. Needs two chips or
more."""


def read(run):
    if run.trace is None:
        return None
    busy = [d["busy_s"] for d in run.trace["per_device"].values()]
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return 100.0 * (max(busy) * len(busy) / sum(busy) - 1.0)
