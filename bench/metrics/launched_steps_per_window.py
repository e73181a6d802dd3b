"""Weave scan steps launched per simulated point-window.

Read from the program's ``repro.steps.launched`` counter: every launch
adds rows x windows x its engine's static scan steps per window, dense
re-runs included, over the point-windows the calls simulated.  Fewer
steps per window should raise ``sim_windows_per_s``.
"""
from metrics import _recorder


def read(ctx):
    got = _recorder.per_call(ctx)
    if got is None:
        return None
    return _recorder.total(got, "repro.steps.launched") / (
        len(got) * ctx["cell"].point_windows)
