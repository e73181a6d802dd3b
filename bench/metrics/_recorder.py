"""What the program's own recorder (`repro.obs.spans`) holds per call.

The metric readers beside this file read the spans and counts that the
grid driver recorded between each call's start and end.  They take the
calls after the profiler stopped, which carry no profiler cost, or
every call when the profiler ran throughout.  A program without the
recorder, or one that recorded nothing in the calls, gives ``None``.
"""


def per_call(ctx):
    """``[(spans, counts), ...]`` for the window's untraced calls."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    calls = ctx["calls"][ctx["traced"]:] or ctx["calls"]
    got = [(spans.spans_between(t0, t1), spans.counts_between(t0, t1))
           for t0, t1, _ in calls]
    return got if any(s or c for s, c in got) else None


def total(got, name: str) -> int:
    """A counter's total over the calls."""
    return sum(counts.get(name, 0) for _, counts in got)
