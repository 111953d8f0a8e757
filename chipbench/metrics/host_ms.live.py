"""host_ms.live: host milliseconds per training step in which the device
was idle inside the benchmark's span around each job (the trainer loop, the
Tier-2 controller's decisions, the job's state, dispatch, the loss and
parameter copies; each job's ``Trainer`` is built in set-up), on the
profiler's clock."""


def read(reading: dict):
    t = reading["trace"]
    if not t["spans"] or not reading["steps"]:
        return None
    return 1e3 * (t["span_s"] - t["span_busy_s"]) / reading["steps"]
