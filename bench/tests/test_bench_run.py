"""Whole runs at a small size on the CPU: the refusal without a chip, a
sound run, the control, and the faults the comparison has to catch."""
import dataclasses
import io
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT, device_spec, small_spec
from harness import compare
from harness.cells import Cell
from harness.runner import run

SEED = 2 ** 31 + 11


def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)


def test_cli_refuses_to_run_without_a_chip():
    p = _cli("--workload", "mess-ddr4-s10.saturated", "--seed", str(SEED),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "accelerator" in p.stderr


def test_cli_refuses_an_unknown_cell():
    p = _cli("--workload", "nonesuch.cell", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 2
    assert p.stdout == ""


def _result(spec, trace=False):
    out, err = io.StringIO(), io.StringIO()
    rc = run(spec, SEED, 0.5, trace, time.perf_counter(),
             require_chip=False, out=out, err=err)
    assert rc == 0
    lines = err.getvalue().strip().splitlines()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    # every compared number is printed last on stderr, with its limit
    assert [ln.split(":")[0] for ln in lines[-len(result["checks"]):]] == \
        [f"check {n}" for n in result["checks"]]
    assert list(result)[-1] == "checks"
    return result


@pytest.fixture
def fresh_programs():
    """Programs traced after a fault is planted, and again after it goes."""
    jax.clear_caches()
    yield
    jax.clear_caches()


CELLS = ["mess-ddr4-s10.saturated", "replay-ddr4-s07.damov6"]
#: test-only configurations of other devices (`conftest.DEVICE_CONFIGS`)
DEVICES = ["mess-ddr5-s10", "mess-hbm2e-s10-2s"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = _result(small_spec(name, paces=4))
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"sim_windows_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert all(c["value"] == 0.0 for c in result["checks"].values())


def test_traced_run_reports_per_layer_metrics():
    result = _result(small_spec("replay-ddr4-s07.damov6"), trace=True)
    assert result["correct"] is True
    # the CPU backend has no device plane: only the static counts
    assert set(result["metrics"]) == {"scan_steps_per_window",
                                      "rerun_share", "compiles_in_window"}


@pytest.mark.parametrize("name", CELLS + DEVICES)
def test_control_is_not_correct(name):
    """The reference in bfloat16 in the program's place fails a limit."""
    spec = device_spec(name) if name in DEVICES else small_spec(name)
    cell = Cell(spec, SEED)
    verdict = compare.judge(cell.kind, [cell.reference(jnp.bfloat16)],
                            cell.reference(), spec["config"]["limits"])
    assert verdict["correct"] is False
    assert verdict["failed"] > 0


# ---- faults planted under the timed path ---------------------------------

def _stuck_tick(monkeypatch):
    """Every DRAM tick returns the controller's state unchanged."""
    from repro.core import dram

    tick = dram.tick

    def stuck(queue, banks, t, **kw):
        return (queue, banks) + tuple(tick(queue, banks, t, **kw)[2:])

    monkeypatch.setattr(dram, "tick", stuck)


def _mess_outputs(monkeypatch, change):
    """`sweep` with its per-pace outputs rewritten by ``change``."""
    from repro.core import mess

    sweep = mess.sweep
    views = ("sim_bw", "sim_lat", "if_bw", "if_lat", "app_bw", "app_lat",
             "chase_lat")

    def broken(cfg, paces, write_mixes):
        res = sweep(cfg, paces=paces, write_mixes=write_mixes)
        return dataclasses.replace(res, **change(
            {v: np.array(getattr(res, v)) for v in views}, cfg, paces,
            write_mixes, sweep))

    monkeypatch.setattr(mess, "sweep", broken)


def _replay_outputs(monkeypatch, change):
    import repro.traces.replay as replay

    suite = replay.replay_suite

    def broken(cfg, batch):
        out = {k: np.array(v) for k, v in suite(cfg, batch).items()}
        return change(out, cfg, batch, suite)

    monkeypatch.setattr(replay, "replay_suite", broken)


def _half_mess(views, cfg, paces, write_mixes, sweep):
    """Half of the paces run; the rest get the mean of that half."""
    k = max(1, len(paces) // 2)
    res = sweep(cfg, paces=paces[:k], write_mixes=write_mixes)
    out = {}
    for v in views:
        a = np.array(getattr(res, v))
        fill = np.repeat(a.mean(axis=1, keepdims=True), len(paces) - k, 1)
        out[v] = np.concatenate([a, fill], axis=1)
    return out


def _half_replay(out, cfg, batch, suite):
    n = len(out["n_rd"])
    k = max(1, n // 2)
    half = suite(cfg, jax.tree_util.tree_map(lambda a: a[:k], batch))
    for key, v in half.items():
        v = np.asarray(v)
        fill = np.repeat(v.mean(axis=0, keepdims=True), n - k, axis=0)
        out[key] = np.concatenate([v, fill.astype(v.dtype)])
    return out


def _altered_mess(views, *_):
    views["sim_lat"][0, -1] *= 1.001
    return views


def _altered_replay(out, *_):
    out["n_rd"][-1] += 1
    return out


FAULTS = {
    "state_unchanged": (_stuck_tick, _stuck_tick),
    "half_batch": (lambda m: _mess_outputs(m, _half_mess),
                   lambda m: _replay_outputs(m, _half_replay)),
    "answer_altered": (lambda m: _mess_outputs(m, _altered_mess),
                       lambda m: _replay_outputs(m, _altered_replay)),
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch, fresh_programs):
    spec = small_spec(name, paces=4)
    plant = FAULTS[fault][spec["traffic"]["kind"] == "replay"]
    plant(monkeypatch)
    result = _result(spec)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


# ---- a program that runs another machine than the configuration states ---

def _program_changed(monkeypatch, change):
    """`Cell.prepare` as it is, then the program's stage rewritten by
    ``change``: the platform group, and so the reference, stay as
    stated."""
    prepare = Cell.prepare

    def changed(self):
        prepare(self)
        self.cfg = change(self.cfg)

    monkeypatch.setattr(Cell, "prepare", changed)


def _all_bank_refresh(cfg):
    dram = dataclasses.replace(cfg.platform.dram, same_bank_refresh=False)
    return dataclasses.replace(
        cfg, platform=dataclasses.replace(cfg.platform, dram=dram))


def _one_socket(cfg):
    return dataclasses.replace(cfg, n_sockets=1)


@pytest.mark.parametrize("device, change", [
    ("mess-ddr5-s10", _all_bank_refresh),
    ("mess-hbm2e-s10-2s", _one_socket),
], ids=["ddr5_all_bank_refresh", "hbm2e_one_socket"])
def test_device_fault_is_not_correct(device, change, monkeypatch):
    _program_changed(monkeypatch, change)
    result = _result(device_spec(device))
    assert result["correct"] is False
    assert result["failed"] > 0
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
