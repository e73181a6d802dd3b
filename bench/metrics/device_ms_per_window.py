"""Device busy time per simulated point-window, in ms.

Busy time is the union of the device-operation intervals in the traced
calls that the trace holds (averaged over the chips), divided by the
point-windows those calls simulated.  It moves ``sim_windows_per_s``:
the window loop and the weave scan (`core/platform._window_step`,
`dram.tick`, `dram.next_event`) are what the device runs.
"""


def read(ctx):
    red = ctx["trace"]
    if not red or not red["call_gap_s"]:
        return None
    calls = len(red["call_gap_s"])
    return red["busy_s"] * 1e3 / (calls * ctx["cell"].point_windows)
