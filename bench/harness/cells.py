"""One cell: its inputs from the seed, the program's call, the reference.

The traffic file's ``kind`` names the program entry one call drives:

* ``mess``   — `repro.core.mess.sweep` over the file's ``paces`` at one
  write mix, drawn by the seed from its ``write_mixes``;
* ``replay`` — `repro.traces.replay.replay_suite` over the file's
  ``apps``, each ``accesses`` long in a ``footprint_lines`` footprint,
  generated from the seed.

The configuration's ``program`` group names the program's ``stage``
and ``preset``; any other key of it is a `StageConfig` override (such
as ``n_sockets``).  The program's resolved stage and the ``platform``
group the reference reads must describe one machine: `Cell.prepare`
refuses a configuration where they differ, before any call.

Every call of a run simulates the same inputs, so one reference run
covers every call of the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import reference, tracegen
from harness.spec import SpecError

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
        self.cfg = program_stage(self.config, self.platform)
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


# ---- the program's stage, held to the platform group --------------------

#: what the reference models of the program's choices that the
#: platform group does not state
_REFERENCE_MODELS = {"clock_mode": "picosecond", "row_hit_cap": 0}


def program_stage(config: dict, platform: reference.Platform):
    """The program's `StageConfig` for ``config``, after checking that it
    and the ``platform`` group describe the same machine.

    Raises `SpecError` for a ``program`` key that `StageConfig` lacks or
    whose type differs from its default, and for the first fact on
    which the resolved stage and the platform group disagree.
    """
    from repro.core import get_stage
    from repro.core.platform import StageConfig

    prog = dict(config["program"])
    stage, preset = prog.pop("stage"), prog.pop("preset")
    fields = {f.name: f for f in dataclasses.fields(StageConfig)}
    for key, value in prog.items():
        if key not in fields:
            raise SpecError(f"{config['name']}: program key {key!r} is not a"
                            " StageConfig field")
        default = fields[key].default
        if type(value) is not type(default):
            raise SpecError(f"{config['name']}: program key {key!r} = "
                            f"{value!r} is not of the type of StageConfig's "
                            f"default {default!r}")
    overrides = dict(windows=platform.windows, warmup=platform.warmup)
    overrides.update(prog)
    try:
        cfg = get_stage(stage, preset=preset, **overrides)
    except ValueError as e:
        raise SpecError(f"{config['name']}: {e}") from None
    facts = program_facts(cfg)
    # what both groups state first, then what the stage resolves
    for key in sorted(facts, key=lambda k: k not in prog):
        value = facts[key]
        want = _REFERENCE_MODELS.get(key, getattr(platform, key, None))
        if value != want or isinstance(value, bool) != isinstance(want,
                                                                  bool):
            where = ("program group states" if key in prog
                     else f"program's stage {stage} on {preset} resolves")
            stated = ("the reference models"
                      if key in _REFERENCE_MODELS else "platform group states")
            raise SpecError(f"{config['name']}: {key} differs: the {where} "
                            f"{value!r}, the {stated} {want!r}")
    return cfg


def program_facts(cfg) -> dict:
    """Every fact the platform group states, as the program's resolved
    `StageConfig` has it (module constants for the frontend's caps)."""
    from repro.core import platform, workload
    from repro.core.timing import DramParams

    cpu, dram, policy = cfg.platform.cpu, cfg.platform.dram, cfg.policy
    wcfg = cfg.workload_config()
    geometry = ("n_channels", "ranks_per_channel", "banks_per_rank",
                "lines_per_row", "rows_per_bank")
    ddr4 = [getattr(DramParams(), g) for g in geometry]
    if cfg.mapping == "skylake_xor":
        # the program serves skylake_xor by the generic fold off DDR4
        address_map = ("skylake_xor"
                       if [getattr(dram, g) for g in geometry] == ddr4
                       else "xor_fold")
    else:
        address_map = cfg.mapping
    timings = [f.name for f in dataclasses.fields(reference.Platform)
               if f.name[0] == "t" and f.name[1].isupper()]
    return dict(
        windows=cfg.windows, warmup=cfg.warmup,
        n_cores=wcfg.n_cores, n_sockets=wcfg.n_sockets,
        socket_channels=wcfg.socket_channels,
        cpu_ps_per_clk=cpu.cpu_ps_per_clk, window_cycles=cpu.window_cycles,
        cache_path_cycles=wcfg.cache_path_cycles,
        noc_req_cycles=wcfg.noc_req_cycles,
        noc_resp_cycles=wcfg.noc_resp_cycles,
        l_ir_init_cycles=cfg.l_ir_init_cycles, pi_latency=cfg.pi_latency,
        pi_keep=platform.PI_KEEP, pi_blend=platform.PI_BLEND,
        prefetch=wcfg.prefetch, pf_shift=wcfg.pf_shift,
        cap_demand=workload.CAP_DEMAND, cap_pf=workload.CAP_PF,
        backlog_max=workload.BACKLOG_MAX, mshr_cap=workload.MSHR_CAP,
        chase_region_bits=workload.CHASE_REGION_BITS,
        n_channels=dram.n_channels, ranks=dram.ranks_per_channel,
        banks_per_rank=dram.banks_per_rank, bank_groups=dram.bank_groups,
        rows_per_bank=dram.rows_per_bank, lines_per_row=dram.lines_per_row,
        address_map=address_map,
        refresh="same_bank" if dram.same_bank_refresh else "all_bank",
        line_bytes=dram.line_bytes, dram_ps_per_clk=dram.dram_ps_per_clk,
        queue_depth=policy.queue_depth, drain_hi=policy.drain_hi,
        drain_lo=policy.drain_lo, mc_extra_ticks=policy.mc_extra_ticks,
        clock_mode=cfg.clock_mode, row_hit_cap=policy.row_hit_cap,
        **{t: getattr(dram, t) for t in timings})
