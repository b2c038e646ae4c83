"""executor.dispatch_ms: mean time a request's executables took to be
called, up to the return of the call (join-index lookups and the enqueue,
not the device's work), in milliseconds, over the requests completed in
the window that dispatched their own share: the sum of the request's
``plan.dispatch`` spans. Read from the program tracer, which records while
the profiler runs; a program without these spans gives nothing."""
from bench import spans


def read(run):
    by = spans.request_spans(run, ("plan.dispatch",))
    if by is None:
        return None
    own = [sum(s.dur for s in ss) for ss in by.values() if ss]
    return 1e3 * sum(own) / len(own)
