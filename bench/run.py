#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload mlp.settled --seed 7 --seconds 30 --trace 0

The cell, its configuration, its traffic mix and its metrics are read
from ``BENCHMARK.json`` at the root of the checkout and from the files
under ``bench/`` named there. The last line of standard output is one
JSON object: ``correct``, ``attempted`` (client updates), ``failed``,
``metrics`` (the end-to-end metrics with ``--trace 0``, the per-layer
ones with ``--trace 1``), ``device`` and, traced, ``breakdown``; its
last key, ``checks``, gives each number compared beside its limit, and
the same lines end standard error. Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_file):
        print(f"bench: no BENCHMARK.json at {ROOT}", file=sys.stderr)
        return 2
    with open(bench_file) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    chips = int(cells[args.workload]["chips"])

    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    # the compile cache: $JAX_COMPILATION_CACHE_DIR when set, else a
    # fixed path inside the checkout
    from bench.lib import checks, harness, jaxcache
    jaxcache.enable()
    cell = harness.Cell(ROOT, args.workload)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    limits = cell.limits()
    numbers = out["numbers"]
    correct = checks.judge(numbers, limits)
    device = {"platform": out["platform"], "kind": out["kind"],
              "count": chips, "memory_peak_bytes": out["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = out.get("busy_s", 0.0)
        device["window_s"] = out.get("window_s", 0.0)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    print("phases " + json.dumps(out["phases"]), file=sys.stderr)
    for k, v in limits.items():
        print(f"check {k} {numbers.get(k)!r} limit {v!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
