"""mfu.sim: the whole sweep's share of the chips' peak, in percent.

Useful FLOPs of the traced sweeps (each returned block subgradient,
coded's full gradient, each suboptimality eval and iterate update,
counted from the shapes by the kind's functions in ``chipbench/kinds``)
over the traced window, over the table's bfloat16 peak times the chips
the cell holds.  The scan runs in emulated float64 and float32, so the
share is far below what bfloat16 work could reach; it still bounds every
kernel inside the sweep."""


def read(reading: dict):
    t = reading["trace"]
    if not reading.get("peak_flops") or t["window_s"] <= 0 or reading["useful_flops"] <= 0:
        return None
    return 100.0 * reading["useful_flops"] / t["window_s"] / reading["peak_flops"]
