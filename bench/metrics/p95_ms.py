"""95th percentile of every request completed in the window, from the
client's submit (or, in an open loop, from when it was due) to the moment
the client saw its result."""
import numpy as np


def read(rec):
    if not rec["latencies_s"]:
        return None
    return float(np.percentile(np.asarray(rec["latencies_s"]) * 1e3, 95))
