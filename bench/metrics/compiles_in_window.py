"""XLA compilations during the measured window (JAX monitoring events).

Set-up warms every shape the cell uses, so this should be 0; a compile
in the window costs ``sim_windows_per_s`` directly.
"""


def read(ctx):
    return ctx["compiles_in_window"]
