#!/usr/bin/env python3
"""The share of a traced window each layer of the scanned StoCFL round
took on the chip, from a raw trace that ``probe.py`` kept: the self time
of the ops under each ``jax.named_scope`` of the round step, the idle
share, what the two together leave unattributed, and the host's
``repro.*`` span shares. One JSON line on standard output.

    python3 bench/tools/probe.py --workload mlp.onboard --seed 11 --seconds 30
    python3 bench/tools/layer_shares.py chiprun_out/probe/mlp.onboard.xplane.pb
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the round step's layer scopes (engine/strategies.py), in step order
SCOPES = ("cohort_gather", "psi_extraction", "merge_pass", "bank_merge",
          "local_update", "aggregation", "objective")


def shares(path: str, chips: int = 1) -> dict:
    """Per-layer shares (%) of the last ``bench.window`` of the trace."""
    from bench.lib import layers
    from bench.lib import trace as tr
    with open(path, "rb") as f:
        scopes = layers.op_scopes(f.read())
    t = tr.load(path)
    lo, hi = t.window()
    run = {"trace": t, "window_ns": (lo, hi), "chips": chips}
    planes = sorted(t.devices)[:chips]
    idle = 100.0 * sum(1.0 - tr.busy_ns(t.devices[p], lo, hi) / (hi - lo)
                       for p in planes) / max(len(planes), 1)
    out = {"window_s": (hi - lo) / 1e9, "idle": idle}
    for s in SCOPES:
        out[s] = layers.device_share(run, s, scopes)
    out["unattributed"] = 100.0 - idle - sum(out[s] or 0.0 for s in SCOPES)
    out["finalize_less_wait"] = layers.host_share(
        run, "repro.scan.finalize", minus=("repro.finalize.wait",))
    out["prepare"] = layers.host_share(run, "repro.scan.prepare")
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="an .xplane.pb that probe.py kept")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()
    print(json.dumps({"trace": os.path.basename(args.trace),
                      **shares(args.trace, args.chips)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
