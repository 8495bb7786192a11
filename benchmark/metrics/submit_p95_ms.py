"""A POST's due send time to its 201 (host clock, ms), 95th percentile
over the window's POSTs; a POST that failed or never answered counts at
the time it was followed for. An open loop only: a top-up's POSTs are
not sent on a schedule."""
import numpy as np


def read(ctx):
    if ctx["mode"] != "open" or not ctx["posts"]:
        return None
    lat = [((p.done if p.done is not None else ctx["t_end"]) - p.due) * 1e3
           for p in ctx["posts"]]
    return float(np.percentile(lat, 95))
