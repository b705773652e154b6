"""Estimator error, %: median over the window's steps of
|predicted - measured| / measured, where predicted is the plan's scored
time (``IterationDetail.predicted_time``) and measured the step's compute
time on the wall clock (``compute_time``)."""
import statistics


def read(ctx):
    errs = [abs(r.predicted_time - r.compute_time) / r.compute_time
            for r in ctx.rows if r.compute_time > 0]
    return 100.0 * statistics.median(errs) if errs else None
