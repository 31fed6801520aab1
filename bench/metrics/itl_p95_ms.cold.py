"""95th percentile of the gaps between consecutive output tokens over the
window's finished requests, from the program's own stamp of each token's
arrival at its handle (``SubmitResult.token_times_s``)."""

import numpy as np

from benchlib import stats


def read(ctx):
    done = stats.done(ctx.records)
    if not done or any(r.get("token_times_s") is None for r in done):
        return None
    gaps = [g * 1e3 for r in done for g in np.diff(r["token_times_s"])]
    return stats.percentile(gaps, 95)
