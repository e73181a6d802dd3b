"""Share of the event engine's scan steps that held an event.

Read from the program's counters: ``repro.steps.event_used`` (the
post-warm-up ``weave_events`` of the event-launched rows, taken from
the event pass) over ``repro.steps.event_budget`` (those rows' budget
steps over the same windows).  The rest of the budget is padding the
scan runs anyway; a fuller budget means less wasted device time per
window.  Cells with no event launch have nothing to read.
"""
from metrics import _recorder


def read(ctx):
    got = _recorder.per_call(ctx)
    if got is None:
        return None
    budget = _recorder.total(got, "repro.steps.event_budget")
    if not budget:
        return None
    return _recorder.total(got, "repro.steps.event_used") / budget
