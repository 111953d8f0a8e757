"""driver_ms.sim: host milliseconds per sweep in which the device was idle
inside the benchmark's span around ``run_convergence_sweep`` (trace
sampling, scan input preparation, result assembly), on the profiler's
clock."""


def read(reading: dict):
    t = reading["trace"]
    if not t["spans"] or not reading["requests"]:
        return None
    return 1e3 * (t["span_s"] - t["span_busy_s"]) / reading["requests"]
