"""Device: the share of the traced window in which no operation ran on
the chip, 100 (1 - busy / window), from the profiler trace."""
import tracing


def read(run):
    t0, t1 = tracing.window_bounds(run.trace)
    return 100.0 * (1.0 - tracing.busy_s(run.trace) / ((t1 - t0) / 1e9))
