"""99th percentile of every gap between consecutive tokens of a request,
stamped on the client's side of ``stream()``, all the window's requests
pooled. The engine delivers a burst at once, so most gaps are near zero
and the tail is the time between two deliveries."""

import numpy as np


def read(run):
    gaps = run.samples.get("token_gaps_ms")
    if not gaps:
        return None
    return float(np.percentile(np.asarray(gaps, np.float64), 99))
