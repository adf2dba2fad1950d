"""cluster_sums_roofline (%): the least time the chip could take for the
Pallas row segment-sum kernel's calls in the window (the cluster means'
member sums over the Psi bank) over the time they took. Per call at K
bank rows of D = |Psi| dimensions:

    FLOPs  K D               (one f32 add per element of the bank)
    bytes  8 K D + 4 K       (the whole (K, D) bank read, as the kernel's
                              block DMA reads it, dead rows included; the
                              (K, D) sums written; the segment ids)

and the least time is the larger of FLOPs / peak FLOP/s and bytes /
peak HBM bytes/s. K is the merge bound of a fresh federation, the power
of two at or above the client count. The kernel's events are found by
its own instruction name (``segment_rows``, or ``segment_rows.<n>``);
a program without the kernel reads None."""
import re

from bench.lib import trace as tr

NAME = re.compile(r"segment_rows(\.\d+)?$")


def min_seconds(k: int, d: int, peak: dict) -> float:
    flops = 1.0 * k * d
    nbytes = 8.0 * k * d + 4.0 * k
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def read(run):
    t = run["trace"]
    if t is None or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    spent, calls = 0.0, 0
    for p in sorted(t.devices)[: run["chips"]]:
        s, n = tr.time_by_name(tr.clip(t.devices[p], lo, hi),
                               lambda s: bool(NAME.match(tr.short_name(s))))
        spent, calls = spent + s, calls + n
    if not calls or spent <= 0:
        return None
    least = calls * min_seconds(run["merge_k"], run["psi_dim"], run["peak"])
    return 100.0 * least / (spent / 1e9)
