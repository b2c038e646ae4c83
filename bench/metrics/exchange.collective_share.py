"""exchange.collective_share: percent of the device's busy time in the
traced window spent in collective ops (all-to-all, all-gather, all-reduce,
reduce-scatter, collective-permute; ``devtrace.reduce``'s
``collective_s``), the mean over the cell's chips: the share of the work
that is the Exchanges' movement between chips."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
