"""Device time under ``step.plan`` (dependence, fits and allocation) per
fleet window, busiest chip, from the trace and the compiled program's op
metadata."""
import scopes


def read(run):
    return scopes.ms_per_window(run, "step.plan")
