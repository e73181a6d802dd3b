"""Host time per call with no operation on any device, in ms.

Measured inside each traced call's ``bench.call`` span: the span's
length less the part of it that device operations cover.  It is the
grid driver's host work (`mess.sweep`, `replay_suite`: routing,
transfers, re-runs, merges) and moves ``sim_windows_per_s``.
"""


def read(ctx):
    red = ctx["trace"]
    if not red or not red["call_gap_s"]:
        return None
    return 1e3 * sum(red["call_gap_s"]) / len(red["call_gap_s"])
