"""device_idle_share (%): the share of the traced window in which no
operation ran on a chip, mean over the cell's chips. Busy time is the
union of the intervals of the ``XLA Ops`` events of the chip's plane."""
from bench.lib import trace as tr


def read(run):
    t = run["trace"]
    if t is None or not t.devices or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    planes = sorted(t.devices)[: run["chips"]]
    idle = [1.0 - tr.busy_ns(t.devices[p], lo, hi) / (hi - lo)
            for p in planes]
    return 100.0 * sum(idle) / len(idle)
