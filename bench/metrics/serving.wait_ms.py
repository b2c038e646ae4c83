"""serving.wait_ms: mean time a request of the window spent queued and
waiting for its batch (``QueryResult.phases`` queue_wait + batch_wait), in
milliseconds, over the requests completed in the window."""


def read(run):
    phases = [r.phases for r in run.completed if r.phases]
    if not phases:
        return None
    return 1e3 * sum(p["queue_wait"] + p["batch_wait"]
                     for p in phases) / len(phases)
