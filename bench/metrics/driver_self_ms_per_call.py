"""The grid driver's own host time per call, in ms.

The length of the program's outermost span in each call
(``repro.mess.sweep``, ``repro.replay.suite`` or ``repro.replay.mixes``)
less the ``*.fetch`` spans inside it, where the host only waits for
the device (`repro.obs.spans.self_seconds`): routing, launches, merges
and the runtime extraction.  Read it against ``host_gap_ms_per_call``;
it moves ``sim_windows_per_s`` where the device waits on the host.
"""
from metrics import _recorder


def read(ctx):
    got = _recorder.per_call(ctx)
    if got is None:
        return None
    from repro.obs import spans

    return 1e3 * sum(spans.self_seconds(s) for s, _ in got) / len(got)
