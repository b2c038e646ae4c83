"""kernel.hash_aggregate_share: percent of the device's busy time in the
traced window spent in the Pallas kernel named ``hash_aggregate`` (its
custom calls are the instructions ``hash_aggregate.<n>``), among the ops
that took the most time (``devtrace.reduce``'s ``device_ops``). A program
whose kernel carries no name gives nothing."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    secs = [s for label, s in run.trace["device_ops"]
            if label.split(":", 1)[0].rsplit(".", 1)[0] == "hash_aggregate"]
    if not secs:
        return None
    return 100.0 * sum(secs) / run.trace["busy_s"]
