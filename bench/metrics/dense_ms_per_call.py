"""Host time per call inside the dense engine's launches, in ms.

The summed length of the program's ``*.dense`` spans (``repro.mess.dense``,
``repro.replay.dense``: the launch, and for the replay the gather of
the re-run rows, up to the results on the host) per call.  The dense
engine scans every DRAM tick, so its share of a call is what an
event-engine change can win for ``sim_windows_per_s``.
"""
from metrics import _recorder


def read(ctx):
    got = _recorder.per_call(ctx)
    if got is None:
        return None
    return 1e3 * sum(s.end - s.start for spans, _ in got for s in spans
                     if s.name.endswith(".dense")) / len(got)
