"""Share of the rows a call launched that were re-run on the dense engine.

Read from the program's counters: ``repro.rows.rerun`` over
``repro.rows.event`` plus ``repro.rows.dense``, as the grid driver
counts them at each launch (`repro.core.platform.count_launch`).  A
re-run row is simulated twice, so it costs ``sim_windows_per_s``.
Unlike ``rerun_share``, it sees the Mess sweep's re-runs too.
"""
from metrics import _recorder


def read(ctx):
    got = _recorder.per_call(ctx)
    if got is None:
        return None
    rows = _recorder.total(got, "repro.rows.event") \
        + _recorder.total(got, "repro.rows.dense")
    return _recorder.total(got, "repro.rows.rerun") / rows if rows else None
