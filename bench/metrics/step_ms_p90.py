"""90th percentile of the window's step times, each the loop's own
``device_step`` span (dispatch, the overlapped prefetch of the next batch
and the block on this step's loss)."""
import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.step_s) * 1e3, 90))
