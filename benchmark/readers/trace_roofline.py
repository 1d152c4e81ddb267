"""A kernel's share of its roofline, from the device trace.

least time = max(ops / peak ops/s, bytes / peak bytes/s) for the lanes the
kernel served in the traced window; share = least time / summed device time
of the kernel's programs. The work per lane is a constant of the textbook
algorithm kept in the metric's own file, so the share reads the same
whatever kernel serves the lane. No kernel time in the trace returns None
(never 0)."""

from ..harness import counters, tracing


def work_per_lane(metric: dict) -> tuple[float, float]:
    w = metric["work_per_lane"]
    ops = 2.0 * w["field_mults"] * w["macs_per_field_mult"]
    return ops, float(w["bytes"])


def read(metric: dict, ctx) -> float | None:
    tr = ctx.trace
    if tr is None:
        return None
    kernel_s = tracing.module_seconds(tr, metric["module_regex"])
    lanes = counters.total(ctx.trace_counters, metric["lanes"])
    if kernel_s <= 0 or lanes <= 0:
        return None
    ops, nbytes = work_per_lane(metric)
    peak = ctx.peaks
    least = max(
        lanes * ops / peak[metric["peak"]],
        lanes * nbytes / peak["hbm_bytes_per_s"],
    )
    return 100.0 * least / kernel_s
