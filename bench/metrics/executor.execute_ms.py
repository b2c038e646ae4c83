"""executor.execute_ms: mean time from a request's dispatch to the scheduler
to its plan's result on the host side (``QueryResult.phases`` execute, which
ends after ``block_until_ready``), in milliseconds, over the requests
completed in the window."""


def read(run):
    phases = [r.phases for r in run.completed if r.phases]
    if not phases:
        return None
    return 1e3 * sum(p["execute"] for p in phases) / len(phases)
