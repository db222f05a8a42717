"""95th percentile, over every call of the window, of the wall time from a
chunk's entry call to its result on the host."""

import numpy as np


def read(run):
    if not run.driver.pulls:
        return None
    t = [(c.end - c.start) * 1e3 for c in run.window.calls]
    return float(np.percentile(t, 95))
