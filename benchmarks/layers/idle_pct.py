"""Device idle share of the traced slice: 1 - (union of device-op
intervals) / slice, from the profiler trace.  Source: device_trace."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
