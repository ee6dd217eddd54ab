"""99th percentile of actual minus scheduled send time of the load
generator: a starved generator, not a slow server."""
import numpy as np


def read(facts):
    lag = facts["gen_lag_s"]
    return None if not len(lag) else float(np.percentile(lag, 99)) * 1e3
