"""Weave scan steps per simulated point-window, from static shapes.

Each point runs the engine the program routes it to: the dense engine
scans one step per DRAM tick of a window, the event engine its static
event budget.  A replay row that the output flags in ``weave_sat`` is
re-run on the dense engine and adds that engine's steps.  The count is
exact; fewer steps per window should raise ``sim_windows_per_s``.
"""


def read(ctx):
    cell = ctx["cell"]
    routes = cell.routes(ctx["calls"][0][2])
    steps = sum(cell.steps_per_window(engine)
                + (cell.steps_per_window("dense") if rerun else 0)
                for engine, rerun in routes)
    return steps / len(routes)
