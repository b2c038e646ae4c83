"""serving.round_block_ms: mean time a request of the window spent queued
behind a serving round of other requests, in milliseconds, over the
requests completed in the window: the part of its ``queue.wait`` span
(submit to dequeue) that ``serve.round`` spans cover. The serving loop
takes no request while it serves a round, so this is the wait that
overlapping rounds would remove; the rest of ``serving.wait_ms`` is the
loop's idle tick and the batching. Read from the program tracer, which
records while the profiler runs; a program without these spans gives
nothing."""
from bench import spans


def read(run):
    rounds = sorted((s.t0, s.t0 + s.dur) for s in spans.program_spans()
                    if s.name == "serve.round")
    waits = spans.request_spans(run, ("queue.wait",))
    if not rounds or waits is None:
        return None
    blocked = [sum(spans.covered(s.t0, s.t0 + s.dur, rounds) for s in ss)
               for ss in waits.values() if ss]
    return 1e3 * sum(blocked) / len(blocked) if blocked else None
