"""Online inter-token gap p95, ms: the run's own ``online_itl_p95_ms``
(``cell.end_to_end``: every gap the online clients received in the
window, failures counted), reported per layer where it spreads too
widely to be bounded end to end. In a traced run the profiler's stop
stalls the engine once inside the window, and that gap counts."""
import math


def read(ctx):
    v = ctx.e2e.get("online_itl_p95_ms")
    if v is None or math.isnan(v):
        return None
    return v
