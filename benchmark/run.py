#!/usr/bin/env python3
"""tpu-bft benchmark: one run of one cell.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process. It needs a TPU (exit 3 and no result line without
one), builds the cell's data from ``--seed``, warms the cell's shapes
(set-up), measures for ``--seconds``, compares what the timed path answered
with the plain reference, and prints one JSON object as its last line:
``correct, attempted, failed, metrics, device`` (+ ``breakdown`` when traced,
and ``checks``: every number compared, beside its limit).

``--rehearse`` runs the same code on the CPU at the sizes of
``benchmark/rehearsal/<cell>.json``; its last line has no ``correct`` key
and it exits 3, so it can never pass for the chip. ``--selfcheck`` checks
the trace reduction against a recorded event list and touches no device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument(
        "--control", default="",
        help="put the named control in the program's place when the "
        "answers are compared (must come out not correct)",
    )
    return ap.parse_args(argv)


class Context:
    """What the per-layer readers read."""

    def __init__(self, cell, device_info):
        self.cell = cell
        self.device = device_info
        self.counters: dict = {}  # deltas over the measured window
        self.trace_counters: dict = {}  # deltas over the traced part
        self.trace: dict | None = None  # tracing.reduce_events(...)
        self.stats: dict = {}  # the driver's own numbers
        self._peaks = None

    @property
    def peaks(self) -> dict:
        if self._peaks is None:
            from benchmark.harness import device

            self._peaks = device.peaks(self.device["kind"])
        return self._peaks


def execute(cell, seed: int, seconds: float, trace: bool, device_info: dict,
            control: str = "", t_process: float | None = None):
    """Everything of a run after the look for a chip. Returns the result
    object; the fault tests under benchmark/tests call this directly."""
    from benchmark.harness import counters, device, spec, tracing

    t_process = T_PROCESS if t_process is None else t_process
    ctx = Context(cell, device_info)
    tracer = tracing.Tracer(
        trace, os.path.join(ROOT, ".bench_trace", cell.name),
        float(cell.mix.get("trace_seconds", 5.0)),
    )
    # the program's own counters: compile ledger, transfers, plane windows
    from cometbft_tpu.libs import devledger, devstats

    devstats.enable()
    devledger.enable()
    if trace:
        # the program's own verify.* events carry the lanes of each launch
        from cometbft_tpu.libs import trace as libtrace

        libtrace.enable(ring=1 << 17)
    driver = spec.load_driver(cell.mix).Driver(cell, seed, tracer)
    imports_s = time.monotonic() - t_process
    try:
        driver.setup(seconds)
        before = counters.snapshot(driver.counters())
        n_compiled = len(devstats.compile_log())
        tracer.on_counters = lambda: counters.snapshot(driver.counters())
        setup_s = time.monotonic() - t_process
        window = driver.run_window(seconds)
        after = counters.snapshot(driver.counters())
        ctx.counters = counters.delta(before, after)
        if tracer.events is not None:
            ctx.trace = tracing.reduce_events(tracer.events)
            ctx.trace_counters = counters.delta(before, tracer.counters_at_stop)
        ctx.stats = window["stats"]
        window.setdefault("notes", {})["compiled_in_window"] = (
            devstats.compile_log()[n_compiled:])
        peak = device.memory_peak_bytes()
        checks = driver.check(window, control, ctx)
    finally:
        # whatever the driver started (a service, a node, a generator
        # child) is stopped and waited for, also when the run failed
        driver.close()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = dict(device_info, memory_peak_bytes=peak)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m).read(m, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.trace is not None:
            dev["busy_s"] = ctx.trace["busy_s"]
            dev["window_s"] = ctx.trace["window_s"]
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result = {
        "correct": bool(correct),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": dev,
    }
    if trace and ctx.trace is not None:
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
    result["checks"] = checks
    marks = [("interpreter, jax, program imports", imports_s)] + list(driver.marks)
    for what, took in marks:
        print(f"set-up {took:8.3f} s  {what}", file=sys.stderr)
    print("window " + json.dumps(
        {**ctx.stats, **window.get("notes", {})}, default=str)[:2000],
        file=sys.stderr)
    return result


def report(result: dict, out=sys.stdout) -> None:
    """Every number compared beside its limit as the last lines of stderr,
    then the result object as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)


def selfcheck() -> int:
    """The trace reduction against recorded event lists whose answers are
    known: one written by hand, one recorded on the chip."""
    from benchmark.harness import tracing

    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        cases = json.load(f)["cases"]
    bad = 0
    for case in cases:
        got = tracing.reduce_events(case["events"])
        for key, want in case["expect"].items():
            if key.startswith("module:"):
                have = tracing.module_seconds(got, key[len("module:"):])
            else:
                have = got[key]
            if isinstance(want, float):
                ok = abs(have - want) <= 1e-12 + 1e-9 * abs(want)
            else:
                ok = have == want
            print(f"selfcheck {case['name']} {key}: {have} (want {want}) "
                  f"{'ok' if ok else 'BAD'}")
            bad += not ok
    return 1 if bad else 0


def main(argv=None) -> int:
    args = _args(argv)
    sys.path.insert(0, ROOT)
    if args.selfcheck:
        return selfcheck()
    if not args.workload:
        print("benchmark: --workload is required", file=sys.stderr)
        return 2
    from benchmark.harness import device, spec

    try:
        import cometbft_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not here: {e!r}", file=sys.stderr)
        return 4
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cell = spec.load_cell(args.workload, rehearsal=True)
        import jax

        info = device.describe(jax.devices())
        result = execute(cell, args.seed, args.seconds or 3.0,
                         bool(args.trace), info, args.control)
        result["rehearsal_correct"] = result.pop("correct")
        report(result)
        return 3
    cell = spec.load_cell(args.workload)
    info = device.require_tpu(cell.chips)
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    result = execute(cell, args.seed, seconds, bool(args.trace), info,
                     args.control)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
