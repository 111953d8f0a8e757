"""idle_share.live: percent of the traced window of live jobs in which no
operation ran on the device (1 - union of device-op intervals / window)."""


def read(reading: dict):
    t = reading["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
