"""p95 latency of the online requests due in the traced window: from each
request's due time on the wall clock to the poll between iterations that
sees it finished.  With some forty requests a 51-s window, the order in
which the seed sends the same lengths moves it by a fifth from seed to
seed, too widely to bound end to end; the profiler slows it further."""
import numpy as np


def read(w):
    if not w.online_latency_ms:
        return None
    return float(np.percentile(np.asarray(w.online_latency_ms, np.float64), 95))
