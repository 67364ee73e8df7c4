"""Device time under ``step.sample`` (the draw and the Fisher-Yates loop)
per fleet window, busiest chip, from the trace and the compiled program's
op metadata."""
import scopes


def read(run):
    return scopes.ms_per_window(run, "step.sample")
