"""Stream tuples answered per second: E k N times the fleet windows whose
answers reached the host, over the whole measured window."""


def read(run):
    return (run.tuples_per_window * run.windows_per_call * len(run.timed)
            / run.window_s)
