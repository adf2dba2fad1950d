#!/usr/bin/env python3
"""Look inside one cell on the chip: compile-time memory of its window
program, which kernels and collectives that program holds, the layout of
a short trace, and the numbers compared. Output goes to standard output
and, for the raw trace, to ``chiprun_out/probe/``.

    python3 bench/tools/probe.py --workload mlp.settled --seed 11 --seconds 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def say(**kw):
    print(json.dumps(kw, default=str), flush=True)


def program_report(fn, carry0, consts) -> dict:
    compiled = fn.lower(carry0, consts).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return {"memory": {f: getattr(ma, f, None) for f in fields},
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count("all-reduce("),
            "all_gathers": text.count("all-gather(")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--tiny", action="store_true",
                    help="a few clients of a narrow model (CPU rehearsal)")
    args = ap.parse_args()
    tiny = ({"federation": {"n_clients": 40, "n_per": 16},
             "model": {"hidden": 64}} if args.tiny else None)
    from bench.lib import jaxcache
    jaxcache.enable()
    import jax
    from repro import engine
    from bench.lib import harness, peaks
    from bench.lib import trace as tr

    cell = harness.Cell(ROOT, args.workload, overrides=tiny)
    if args.tiny:
        cell.traffic = dict(cell.traffic, warmup_rounds=30)
    dev = jax.devices()[0]
    say(phase="start", kind=dev.device_kind, devices=len(jax.devices()),
        limit=(dev.memory_stats() or {}).get("bytes_limit"))
    t = time.perf_counter()
    prog = harness.Program(cell, args.seed)
    say(phase="program", seconds=time.perf_counter() - t,
        n_params=prog.n_params, m=prog.m)
    t = time.perf_counter()
    drv = cell.driver(prog, cell.traffic)
    say(phase="traffic-setup", seconds=time.perf_counter() - t,
        since_start=time.perf_counter() - T0,
        settled=getattr(drv, "settled", None),
        all_seen=getattr(drv, "all_seen", None),
        n_seen=getattr(drv, "n_seen", None),
        peak_bytes=harness.memory_peak(cell.chips))
    fn, c0, cs, _ = engine.scan_program(*drv.scan_args())
    say(phase="window-program", **program_report(fn, c0, cs))
    del fn, c0, cs
    os.makedirs(os.path.join(ROOT, "chiprun_out", "probe"), exist_ok=True)
    keep = os.path.join(ROOT, "chiprun_out", "probe",
                        f"{args.workload}.xplane.pb")
    with tr.recording(keep) as rec:
        units, rounds, window_s, compiles = harness.run_window(
            drv, args.seconds)
    trace = rec["trace"]
    say(phase="window", units=units, rounds=rounds, window_s=window_s,
        compiles=compiles, updates_per_s=rounds * prog.m / window_s,
        peak_bytes=harness.memory_peak(cell.chips))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(keep)
    for plane in pd.planes:
        lines = list(plane.lines)
        say(plane=plane.name, lines=[(l.name, len(list(l.events)))
                                     for l in lines][:12])
        for l in lines[:6]:
            for e in list(l.events)[:2]:
                say(line=l.name, event=e.name, start=e.start_ns,
                    dur=e.duration_ns, stats=dict(e.stats))
    lo, hi = trace.window()
    say(window_ns=[lo, hi], host_spans=len(trace.host))
    for p, evs in sorted(trace.devices.items()):
        by = {}
        for e in tr.clip(evs, lo, hi):
            k = (e.name, e.category)
            by[k] = by.get(k, 0.0) + e.end - e.start
        top = sorted(by.items(), key=lambda kv: -kv[1])[:25]
        say(device=p, events=len(evs), busy_s=tr.busy_ns(evs, lo, hi) / 1e9,
            top=[[n, c, s / 1e9] for (n, c), s in top])
        odd = sorted({e.name for e in evs
                      if any(x in e.name.lower() for x in
                             ("custom", "candidates", "pallas", "mosaic",
                              "all-reduce", "all-gather"))})
        say(device=p, named=odd[:40])
    say(breakdown=harness.breakdown(trace, lo, hi))
    handoff, before = drv.handoff, drv.rounds_before
    omega0, ecfg = prog.omega0, prog.ecfg
    drv.release()
    del drv, prog
    import gc
    gc.collect()
    t = time.perf_counter()
    numbers = harness.reference_numbers(cell, args.seed, handoff, before,
                                        omega0, ecfg)
    say(phase="reference", seconds=time.perf_counter() - t, rounds=before,
        numbers=numbers,
        peak=None if args.tiny else peaks.peak(dev.device_kind))
    return 0


if __name__ == "__main__":
    sys.exit(main())
