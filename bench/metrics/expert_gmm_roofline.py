"""expert_gmm_roofline (%): the least time the chip could take for the
expert layer's grouped-matmul calls (JAX's megablox ``gmm`` Pallas
kernel, ops named ``gmm.*``) in the window over the time they took.

Every call multiplies the rows one client batch routes to the experts
held here by one of their (d, f) or (f, d) weights: the forward's gate,
up and down matmuls, their rematerialised copies and the backward's
activation gradients alike. At r rows, d model width, f expert width and
E_held experts a call needs

    FLOPs  2 r d f
    bytes  4 E_held d f + 4 r (d + f)   (every held expert's f32 weight
                                         read once, the rows read and
                                         the product written)

and the least time is the larger of FLOPs / peak FLOP/s and bytes /
peak HBM bytes/s. r is the expected count: a batch's tokens (n_per x T)
times top-k x E_held / n_routed.

A reader sees the trace and the run's view, which names neither the cell
nor its configuration, so the sizes come from the configurations of the
cells this metric lists in BENCHMARK.json. Where those cells' sizes
differ, the reader cannot tell which applies and returns None. Two
changes to the harness's view would lift both limits: the cell's
configuration (sizes of the cell measured) and the window's round
records, whose ``moe_rows`` counter holds the rows actually routed to
the held experts, to replace the expected r."""
import json
import os

from bench.lib import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "gmm"


def sizes(root: str = ROOT):
    """(rows r, d, f, E_held) of one call, the same in every cell this
    metric lists; None where the listed cells' sizes differ."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    metric = {m["name"]: m for m in bench["per_layer"]}["expert_gmm_roofline"]
    cells = {w["name"]: w for w in bench["workloads"]}
    confs = {c["name"]: c for c in bench["configs"]}
    found = []
    for name in metric["workloads"]:
        with open(os.path.join(root, confs[cells[name]["config"]]["file"])
                  ) as fh:
            s = _call_sizes(json.load(fh))
        if s not in found:
            found.append(s)
    return found[0] if len(found) == 1 else None


def _call_sizes(cfg: dict) -> dict:
    model, fed = cfg["model"], cfg["federation"]
    held = int(model["experts_held"])
    tokens = int(fed["n_per"]) * int(model["input_shape"][0])
    rows = (tokens * int(model["num_experts_per_tok"]) * held
            / int(model["n_routed_experts"]))
    return {"rows": rows, "d": int(model["hidden_size"]),
            "f": int(model["moe_intermediate_size"]), "held": held}


def min_seconds(rows: float, d: int, f: int, held: int, peak: dict) -> float:
    flops = 2.0 * rows * d * f
    nbytes = 4.0 * held * d * f + 4.0 * rows * (d + f)
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def _is_gmm(text: str) -> bool:
    name = tr.short_name(text)
    return name == NAME or name.startswith(NAME + ".")


def read(run):
    t = run["trace"]
    if t is None or "window_ns" not in run:
        return None
    lo, hi = run["window_ns"]
    spent, calls = 0.0, 0
    for p in sorted(t.devices)[: run["chips"]]:
        s, n = tr.time_by_name(tr.clip(t.devices[p], lo, hi), _is_gmm)
        spent, calls = spent + s, calls + n
    call = sizes()
    if not calls or spent <= 0 or call is None:
        return None
    least = calls * min_seconds(peak=run["peak"], **call)
    return 100.0 * least / (spent / 1e9)
