#!/usr/bin/env python3
"""One cell's window under the profiler, its device idle time divided by
the program's own ``repro:`` spans (``bench/spans.py``).

    python3 bench/span_window.py --workload <cell> --seed <n> --seconds <s> \
        [--save <events.json.gz>]

Run from the root of a checkout, on as many chips as the cell asks for.
After the entry's warm-up it runs an untraced window of ``--seconds`` and
then the traffic file's ``trace_iterations`` under the profiler, both
through the program's own executor (no ``bench:region`` spans, so the
Ledger's counters see every call). It prints one JSON line: seconds per
iteration of each window, the traced window's idle by span kind, the share
of idle under a span finer than a step, and the Ledger's ``readbacks`` and
``syncs`` per iteration. ``--save`` writes the traced window's events (the
device ops and modules and every host span, with their stats), which
``bench/spans.py`` reads back; the recorded-trace tests hold slices of
such files (``window_events`` with a shorter window).
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as harness  # noqa: E402
from bench import spans  # noqa: E402
from bench import trace as tr  # noqa: E402


def ledger_of(entry):
    """The Ledger the entry's calls land on: one device's, where the
    replay keeps one per device (each records every call)."""
    sharded = getattr(entry, "sharded", None)
    return sharded.ledgers[0] if sharded is not None else entry.executor.ledger


def counted(entry, seconds, units, trace_dir=None) -> dict:
    """Run the entry's closed loop; its record plus the Ledger's counters
    over it."""
    import jax
    led = ledger_of(entry)
    r0, s0 = led.readbacks, led.syncs
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        w = entry.window(seconds=seconds, units=units, offer=lambda a: None,
                         traced=False)
    if trace_dir:
        jax.profiler.stop_trace()
    n = w["iterations"]
    return {"s_per_iter": w["wall_s"] / n, "iterations": n,
            "region_calls_per_iter": w["region_calls"] / n,
            "readbacks_per_iter": (led.readbacks - r0) / n,
            "syncs_per_iter": (led.syncs - s0) / n}


def window_events(events, lo, hi):
    """The events a reduction of ``[lo, hi]`` reads: device ops and
    modules, and host spans, that overlap it, with ``bench:window`` cut to
    it."""
    keep = (tr.OPS_LINE, tr.MODULES_LINE)
    out = []
    for e in events:
        if e.end <= lo or e.start >= hi:
            continue
        if e.name == tr.WINDOW_SPAN:
            e = spans.Event(e.plane, e.line, e.name, lo, hi)
        if e.line in keep or (e.plane == "/host:CPU" and (
                e.name.startswith(spans.PREFIX)
                or e.name.startswith(tr.SPAN_PREFIX))):
            out.append(e)
    return out


def measure(workload: str, seed: int, seconds: float, save=None) -> dict:
    cell = harness.load_cell(workload)
    config, traffic = cell["config_data"], cell["traffic_data"]
    dev = harness.device_check(cell["chips"])
    harness.configure_cache()
    entry = importlib.import_module(
        f"bench.entries.{traffic['entry']}").Entry(config, traffic, seed)
    t0 = time.perf_counter()
    entry.warm_up()
    harness.log(f"[setup] warm-up {time.perf_counter() - t0:.3f} s")
    untraced = counted(entry, seconds, None)
    trace_dir = tempfile.mkdtemp(prefix="span-trace-")
    try:
        traced = counted(entry, None, traffic["trace_iterations"], trace_dir)
        events = spans.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    entry.release()
    lo, hi = tr.window_of(events)
    summary = tr.reduce(events)
    idle = spans.idle_by_span(events)
    if save:
        spans.save(window_events(events, lo, hi), save)
    return {"workload": workload, "seed": seed, "device": dev,
            "untraced": untraced, "traced": traced,
            "window_s": summary.window_s, "idle_s": summary.idle_s,
            "idle_by_span": idle, "coverage": spans.coverage(idle)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save")
    args = ap.parse_args(argv)
    print(json.dumps(measure(args.workload, args.seed, args.seconds,
                             args.save)), flush=True)


if __name__ == "__main__":
    main()
