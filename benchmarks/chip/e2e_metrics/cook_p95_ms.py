"""95th percentile of the latency of every request of the window, each
from its due time until its whole answer is at the client (ms); a request
that failed or never came counts as the window plus the drain."""

import numpy as np


def read(ctx: dict):
    recs = ctx["records"]
    if not recs:
        return None
    worst = ctx["seconds"] + ctx["drain_s"]
    lat = [r["end"] - r["due"] if "answer" in r else worst for r in recs]
    return float(np.percentile(np.asarray(lat), 95)) * 1e3
