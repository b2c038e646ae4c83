"""executor.device_wait_ms: mean time a request waited on the device for
what its executables enqueued (``block_until_ready``), in milliseconds,
over the requests completed in the window that dispatched their own share:
the sum of the request's ``plan.device_wait`` spans. Read from the program
tracer, which records while the profiler runs; a program without these
spans gives nothing."""
from bench import spans


def read(run):
    by = spans.request_spans(run, ("plan.device_wait",))
    if by is None:
        return None
    own = [sum(s.dur for s in ss) for ss in by.values() if ss]
    return 1e3 * sum(own) / len(own)
