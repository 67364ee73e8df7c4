"""Device busy time (union of op intervals) per fleet window, busiest
chip, from the trace."""
import readings


def read(run):
    return readings.step_ms_per_window(run)
