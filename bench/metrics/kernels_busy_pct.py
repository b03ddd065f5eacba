"""Pallas kernels: the device time of the program's three kernels
(``gains``, ``pair_scores``, ``pins_count``) as a share of the device's
busy time in the traced window. Nothing when no kernel ran."""
import tracing


def read(run):
    n, kernel_s = tracing.op_time(run.trace, tracing.is_kernel)
    busy = tracing.busy_s(run.trace) * len(run.trace["device"])
    return 100.0 * kernel_s / busy if n and busy > 0 else None
