"""Front end (``serving/frontend.py``): how late ``AsyncFrontend.submit``
returned against the request's due time, 90th percentile, in ms.

Source: the generator's host clock.  The front end's pump runs the
engine's blocking ``step()`` on the event loop, so a request that falls
due mid-step is submitted when the step ends.  Moves ``ttft_p90_ms``.
Open-loop cells only (a closed-loop caller sends when it is ready).
"""
import numpy as np


def read(run):
    if run.loop != "open":
        return None
    lags = [(r.t_submit - r.t_due) * 1e3 for r in run.reqs]
    if not lags:
        return None
    return float(np.percentile(lags, 90))
