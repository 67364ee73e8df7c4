"""Host time per call: the harness span around ``run`` less the device busy
time inside it (busiest chip), from the trace."""
import readings


def read(run):
    return readings.host_ms_per_call(run)
