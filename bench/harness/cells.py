"""One cell: its inputs from the seed, the program's call, the reference.

The traffic file's ``kind`` names the program entry one call drives:

* ``mess``   — `repro.core.mess.sweep` over the file's ``paces`` at one
  write mix, drawn by the seed from its ``write_mixes``;
* ``replay`` — `repro.traces.replay.replay_suite` over the file's
  ``apps``, each ``accesses`` long in a ``footprint_lines`` footprint,
  generated from the seed.

Every call of a run simulates the same inputs, so one reference run
covers every call of the window.
"""
from __future__ import annotations

import numpy as np

from harness import reference, tracegen

#: the views `sweep` returns, as the reference names them
_SWEEP_VIEWS = {"sim_bw": "sim_bw_gbs", "sim_lat": "sim_lat_ns",
                "if_bw": "if_bw_gbs", "if_lat": "if_lat_ns",
                "app_bw": "app_bw_gbs", "app_lat": "app_lat_ns",
                "chase_lat": "chase_lat_ns"}
_REPLAY_KEYS = reference.VIEWS + ("n_rd", "n_wr", "runtime_windows", "done",
                                  "weave_sat")


class Cell:
    """A configuration under a traffic mix, at one seed."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.config, self.traffic = spec["config"], spec["traffic"]
        self.kind = self.traffic["kind"]
        self.windows = int(self.config["windows"])
        self.platform = reference.Platform.from_config(self.config)
        rng = np.random.default_rng(seed)
        if self.kind == "mess":
            self.paces = tuple(int(p) for p in self.traffic["paces"])
            mixes = self.traffic["write_mixes"]
            self.write_mix = int(mixes[rng.integers(len(mixes))])
            self.n_points = len(self.paces)
        elif self.kind == "replay":
            self.apps = tracegen.make_apps(
                self.traffic["apps"], int(self.traffic["accesses"]),
                int(self.traffic["footprint_lines"]), seed)
            self.n_points = len(self.apps)
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}; "
                             "one of ['mess', 'replay']")
        self.point_windows = self.n_points * self.windows
        self.cfg = self.batch = None

    def describe(self) -> str:
        if self.kind == "mess":
            return f"paces {list(self.paces)} at write mix {self.write_mix}/64"
        return f"apps {self.traffic['apps']}"

    def prepare(self) -> None:
        """The program's stage configuration and device-side inputs."""
        from repro.core import get_stage

        prog = self.config["program"]
        self.cfg = get_stage(prog["stage"], preset=prog["preset"],
                             windows=self.windows,
                             warmup=int(self.config["warmup"]))
        if self.kind == "replay":
            from repro.traces import make_trace, stack_traces

            self.batch = stack_traces([make_trace(*a) for a in self.apps])

    def call(self) -> dict:
        """One timed call of the program; host numpy arrays."""
        if self.kind == "mess":
            from repro.core import mess

            res = mess.sweep(self.cfg, paces=self.paces,
                             write_mixes=(self.write_mix,))
            return {ref: np.asarray(getattr(res, view))[0]
                    for view, ref in _SWEEP_VIEWS.items()}
        import repro.traces.replay as replay_mod

        out = replay_mod.replay_suite(self.cfg, self.batch)
        return {k: np.asarray(out[k]) for k in _REPLAY_KEYS}

    def release(self) -> None:
        """Drop the device-side inputs before the reference runs."""
        self.batch = None

    def reference(self, fdt=None) -> dict:
        """The plain reference over the same inputs (float32 unless
        ``fdt`` says otherwise)."""
        import jax.numpy as jnp

        fdt = jnp.float32 if fdt is None else fdt
        if self.kind == "mess":
            return reference.mess(self.platform, self.paces, self.write_mix,
                                  fdt)
        return reference.replay(self.platform, self.apps, fdt)

    # ---- what the program ran, from static shapes -----------------------

    def routes(self, out: dict) -> list:
        """Per point: ``(first engine, re-run on the dense engine)``.

        A Mess point goes where the public `mess.event_covers` sends
        it; `sweep` does not report its re-runs, so none are counted.
        Every replay row takes the event engine first and the rows the
        output flags in ``weave_sat`` are re-run dense.
        """
        if self.cfg.weave != "event":
            return [("dense", False)] * self.n_points
        if self.kind == "mess":
            from repro.core import mess

            return [("event" if mess.event_covers(self.cfg, p) else "dense",
                     False) for p in self.paces]
        return [("event", bool(s > 0)) for s in out["weave_sat"]]

    def steps_per_window(self, engine: str) -> int:
        if engine == "event":
            return self.cfg.event_budget()
        return self.cfg.clock().ticks_per_window_static
