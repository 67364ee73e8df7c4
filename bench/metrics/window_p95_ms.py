"""95th percentile of the time from handing a call its windows to its
answers on the host, over every call of the window."""
import numpy as np

import readings


def read(run):
    return float(np.percentile(readings.latencies_ms(run), 95))
