"""Device idle milliseconds per admission: the time inside the scheduler's
``serve.admit`` host spans in which no operation ran on the chip (the gaps
``idle_share.serve`` counts).  Nothing is read unless the trace holds one
admission span per request of the window."""
import bisect

SPAN = "serve.admit"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window() is None or not tr.device_ops:
        return None
    from bench import trace as T
    lo, hi = tr.window()
    spans = T.named(tr.host, SPAN, lo, hi)
    n = ctx["window"].attempted
    if not spans or len(spans) != n:
        return None
    gaps = T.gaps(tr.busy_ops(sorted(tr.device_ops)[0]), lo, hi)
    ends = [e for _, e in gaps]
    idle = 0.0
    for _, s, e in spans:
        # gaps are disjoint and in order: start at the first that ends after s
        for gs, ge in gaps[bisect.bisect_right(ends, s):]:
            if gs >= e:
                break
            idle += min(e, ge) - max(s, gs)
    return 1e3 * idle * 1e-9 / n
