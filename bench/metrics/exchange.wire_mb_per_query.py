"""exchange.wire_mb_per_query: mean megabytes (1e6 bytes) that one chip
received from the other chips through the Exchanges of a request's plans,
over the requests completed in the window that dispatched their own share:
the sum of the ``exchange_bytes`` of the request's ``plan.dispatch`` spans.
The program counts them from each plan's static buffer shapes, routed
padding included (``planner.exchange_wire``). Read from the program
tracer, which records while the profiler runs; a program whose spans carry
no such count gives nothing."""
from bench import spans


def read(run):
    by = spans.request_spans(run, ("plan.dispatch",))
    if by is None:
        return None
    own = [[dict(s.args).get("exchange_bytes") for s in ss]
           for ss in by.values() if ss]
    if not own or any(b is None for bs in own for b in bs):
        return None
    return sum(sum(bs) for bs in own) / len(own) / 1e6
