"""95th percentile of the window's step times, each the host's clock
between two steps' ends. Over the 17 to 22 steps of a window this is close
to the maximum: a per-layer reading, judged by nobody."""
import statistics


def read(r):
    ms = r["report"]["step_ms"]
    if len(ms) < 2:
        return None
    return statistics.quantiles(ms, n=20, method="inclusive")[-1]
