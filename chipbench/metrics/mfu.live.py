"""mfu.live: the whole training step's share of the chips' peak, in percent.

Useful FLOPs of the traced jobs' steps (every group's loss and gradient,
the cache update and the iterate update, counted from the shapes by the
kind's ``step_flops`` in ``chipbench/kinds/<kind>_live.py``) over the traced
window, over the table's bfloat16 peak times the chips used.  The step runs
in float32, so the share is far below what bfloat16 work could reach; it
still bounds every kernel inside the step."""


def read(reading: dict):
    t = reading["trace"]
    if not reading.get("peak_flops") or t["window_s"] <= 0 or reading["useful_flops"] <= 0:
        return None
    return 100.0 * reading["useful_flops"] / t["window_s"] / reading["peak_flops"]
