"""p95 time to first token of the online requests due in the traced window,
from each request's due time on the wall clock to the poll between
iterations that first sees a token.  The runtime returns once per training
iteration, so a first token is seen a whole iteration late at worst: the
p95 moves in steps of an iteration, which is why it is read here and not
bounded as an end-to-end metric."""

import numpy as np


def read(w):
    if not w.online_ttft_ms:
        return None
    return float(np.percentile(np.asarray(w.online_ttft_ms, np.float64), 95))
