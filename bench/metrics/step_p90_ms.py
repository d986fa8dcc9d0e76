"""90th percentile, over the window steps, of the wall time from one batch
request to the next: data-wait, the host's work and the device step."""
import numpy as np


def read(run):
    return float(np.percentile(run.step_s, 90)) * 1e3
