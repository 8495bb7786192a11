"""How late the open-loop generator sent its POSTs against their
schedule (host clock, ms), 95th percentile over the window's POSTs. A
starved generator must not read as a fast server."""
import numpy as np


def read(ctx):
    if ctx["mode"] != "open":
        return None
    lags = [(p.sent - p.due) * 1e3 for p in ctx["posts"]
            if p.sent is not None]
    return float(np.percentile(lags, 95)) if lags else None
