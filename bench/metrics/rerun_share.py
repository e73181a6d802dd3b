"""Share of a replay call's rows re-run on the dense engine.

The rows `replay_suite` flags in ``weave_sat`` ran the event engine and
then the dense engine again (`traces/replay._replay_exact`): wasted
work that costs ``sim_windows_per_s``.  Exact.  `mess.sweep` does not
report its re-runs, so Mess cells have nothing to read.
"""


def read(ctx):
    cell = ctx["cell"]
    if cell.kind != "replay":
        return None
    routes = cell.routes(ctx["calls"][0][2])
    return sum(rerun for _, rerun in routes) / len(routes)
