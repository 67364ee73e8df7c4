"""The stream_stats kernel's share of its roofline, at unpadded shapes."""
import readings


def read(run):
    return readings.kernel_roofline(run, "stream_stats_fleet")
