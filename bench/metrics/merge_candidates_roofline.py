"""merge_candidates_roofline (%): the least time the chip could take for
the Pallas merge-candidates kernel's calls in the window over the time
they took. Per call at K cluster rows of D = |Psi| dimensions:

    FLOPs  2 K^2 D              (the K x K cosine Gram matrix)
    bytes  4 K D + 4 K^2        (the means read once, the f32 adjacency)

and the least time is the larger of FLOPs / peak FLOP/s and bytes /
peak HBM bytes/s. K is the merge bound of a fresh federation, the power
of two at or above the client count. The kernel's events are found by
the instruction name the trace gives them (the HLO text of an op that
reads the kernel's output names the kernel too, so only the op's own name
is matched)."""
from bench.lib import trace as tr

NAMES = ("merge_candidates", "candidates_kernel")


def min_seconds(k: int, d: int, peak: dict) -> float:
    flops = 2.0 * k * k * d
    nbytes = 4.0 * k * d + 4.0 * k * k
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def read(run):
    t = run["trace"]
    if t is None or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    spent, calls = 0.0, 0
    for p in sorted(t.devices)[: run["chips"]]:
        s, n = tr.time_by_name(tr.clip(t.devices[p], lo, hi),
                               lambda s: tr.short_name(s).startswith(NAMES))
        spent, calls = spent + s, calls + n
    if not calls or spent <= 0:
        return None
    least = calls * min_seconds(run["merge_k"], run["psi_dim"], run["peak"])
    return 100.0 * least / (spent / 1e9)
