"""scan_ms.sim: device milliseconds per sweep in the fused scan's
executables (the jitted ``_run_scan``, one per method), summed over their
events in the profiler trace and divided by the traced sweeps."""


def read(reading: dict):
    t = reading["trace"]
    s = sum(sec for name, sec in t["modules"].items() if "_run_scan" in name)
    if s <= 0 or not reading["requests"]:
        return None
    return 1e3 * s / reading["requests"]
