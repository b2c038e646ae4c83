"""client.latency_p50_ms: the 50th percentile (numpy's linear one) of the
time from a stream's submit to the answer in its hand, in milliseconds, over
the queries completed in the window. The streams run a closed loop that
keeps the chip saturated, so this tail swings with the order in which the
streams' queries meet: a per-layer reading, not a bounded one."""
import numpy as np


def read(run):
    lat = [(r.t_done - r.t_submit) * 1e3 for r in run.completed]
    return float(np.percentile(lat, 50)) if lat else None
