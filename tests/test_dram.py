"""Cycle-accurate DRAM model: DDR4 protocol-legality invariants.

We drive `dram.tick` directly with crafted queues and verify the state
machine respects the JEDEC timing set (the paper's premise is that the
memory simulator itself honors Verilog timings — the bugs live in the
interface; our DRAM model must therefore be timing-legal).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram
from repro.core.dram import SchedulerPolicy
from repro.core.timing import DramParams

D = DramParams()
POL = SchedulerPolicy()


def mk_queue(entries):
    """entries: list of dicts(channel, fbank, row, is_write, arrival)."""
    q = dram.init_queue(D, POL)
    for i, e in enumerate(entries):
        c = e["channel"]
        q = dram.QueueState(
            valid=q.valid.at[c, i].set(1),
            is_write=q.is_write.at[c, i].set(int(e.get("is_write", 0))),
            arrival=q.arrival.at[c, i].set(e.get("arrival", 0)),
            issue_cycle=q.issue_cycle.at[c, i].set(0),
            fbank=q.fbank.at[c, i].set(e["fbank"]),
            row=q.row.at[c, i].set(e["row"]),
            is_chase=q.is_chase.at[c, i].set(0),
        )
    return q


def run_ticks(q, b, n, start=0):
    served = []
    for t in range(start, start + n):
        q, b, st = dram.tick(q, b, jnp.int32(t), dram=D, policy=POL,
                             tick2cpu_num=750, tick2cpu_den=1,
                             cpu_ps_per_clk=476)
        # TickStats is per-channel (C,); reduce to per-tick totals
        served.append((t, int(st.served_rd.sum()), int(st.served_wr.sum())))
    return q, b, served


def test_act_to_cas_respects_trcd():
    """A read to a closed row must wait tRCD after the ACT."""
    q = mk_queue([dict(channel=0, fbank=0, row=5)])
    b = dram.init_banks(D)
    q, b, served = run_ticks(q, b, 60)
    rd_ticks = [t for t, r, w in served if r > 0]
    assert len(rd_ticks) == 1
    # ACT issues at t=0; CAS legal at t=tRCD
    assert rd_ticks[0] == D.tRCD


def test_row_hit_is_immediate():
    q = mk_queue([dict(channel=0, fbank=0, row=5)])
    b = dram.init_banks(D)._replace(
        open_row=dram.init_banks(D).open_row.at[0, 0].set(5))
    q, b, served = run_ticks(q, b, 10)
    rd_ticks = [t for t, r, w in served if r > 0]
    assert rd_ticks[0] == 0


def test_row_miss_needs_pre_act_cas():
    """Conflict: open row 3, request row 5 -> PRE + tRP + ACT + tRCD."""
    b0 = dram.init_banks(D)
    b = b0._replace(open_row=b0.open_row.at[0, 0].set(3))
    q = mk_queue([dict(channel=0, fbank=0, row=5)])
    q, b, served = run_ticks(q, b, 80)
    rd_ticks = [t for t, r, w in served if r > 0]
    # PRE at 0, ACT at tRP, CAS at tRP + tRCD
    assert rd_ticks[0] == D.tRP + D.tRCD


def test_bus_serializes_cas():
    """Two row hits to different banks on one channel: the shared data
    bus forces >= tBL spacing between CAS grants."""
    b0 = dram.init_banks(D)
    open_row = b0.open_row.at[0, 0].set(1).at[0, 1].set(1)
    b = b0._replace(open_row=open_row)
    q = mk_queue([dict(channel=0, fbank=0, row=1),
                  dict(channel=0, fbank=1, row=1)])
    q, b, served = run_ticks(q, b, 20)
    rd_ticks = [t for t, r, w in served if r > 0]
    assert len(rd_ticks) == 2
    assert rd_ticks[1] - rd_ticks[0] >= D.tBL


def test_faw_limits_activation_rate():
    """>4 ACTs to one rank within tFAW must be delayed (tFAW window)."""
    q = mk_queue([dict(channel=0, fbank=i, row=7) for i in range(6)])
    b = dram.init_banks(D)
    q, b, served = run_ticks(q, b, 120)
    # collect ACT-equivalents: the first CAS per bank happened tRCD
    # after its ACT; reconstruct ACT times
    rd_ticks = sorted(t for t, r, w in served if r > 0)
    act_ticks = [t - D.tRCD for t in rd_ticks]
    # 5th activation must fall outside the first ACT's tFAW window
    assert act_ticks[4] >= act_ticks[0] + D.tFAW


def test_channels_are_independent():
    q = mk_queue([dict(channel=0, fbank=0, row=5),
                  dict(channel=3, fbank=0, row=9)])
    b = dram.init_banks(D)
    q, b, served = run_ticks(q, b, 40)
    # both channels serve at the same tick (no cross-channel coupling)
    assert max(r for _, r, _ in served) == 2


def test_refresh_blocks_rank():
    """At tREFI the rank refreshes; reads stall for tRFC."""
    b0 = dram.init_banks(D)
    # force refresh deadline to t=5 on rank 0 of channel 0
    b = b0._replace(next_ref=b0.next_ref.at[0, 0].set(5),
                    open_row=b0.open_row.at[0, 0].set(5))
    q = mk_queue([dict(channel=0, fbank=0, row=5, arrival=6)])
    q, b, served = run_ticks(q, b, 600)
    rd_ticks = [t for t, r, w in served if r > 0]
    # refresh closed the row at t=5; ACT cannot start before 5 + tRFC
    assert rd_ticks[0] >= 5 + D.tRFC + D.tRCD


def test_write_drain_hysteresis():
    """Writes are buffered until the high watermark, then drained."""
    entries = [dict(channel=0, fbank=i % 4, row=1, is_write=1)
               for i in range(POL.drain_hi + 2)]
    q = mk_queue(entries)
    b = dram.init_banks(D)
    q, b, served = run_ticks(q, b, 400)
    wr_total = sum(w for _, r, w in served)
    assert wr_total >= POL.drain_hi - POL.drain_lo  # drained a batch


def test_next_event_is_a_lower_bound():
    """Property: `dram.next_event` never reports a horizon past real
    work — for every channel, ticking the frozen state at any time
    strictly before the reported event grants nothing and moves no
    state (the event-horizon weave engine's correctness premise)."""
    from _proptest import forall

    tick_kw = dict(dram=D, policy=POL, tick2cpu_num=750, tick2cpu_den=1,
                   cpu_ps_per_clk=476)

    @forall(n_cases=12,
            case_seed=lambda rng: int(rng.integers(0, 1 << 30)))
    def prop(case_seed):
        rng = np.random.default_rng(case_seed)
        entries = [dict(channel=int(rng.integers(0, D.n_channels)),
                        fbank=int(rng.integers(0, D.banks_per_channel)),
                        row=int(rng.integers(0, 64)),
                        is_write=int(rng.integers(0, 2)),
                        arrival=int(rng.integers(0, 48)))
                   for _ in range(int(rng.integers(1, 9)))]
        q = mk_queue(entries)
        b = dram.init_banks(D)
        t0 = int(rng.integers(0, 40))
        q, b, _ = run_ticks(q, b, t0)          # a reachable mid-flight state
        end = t0 + 1 + int(rng.integers(1, 20000))
        ev = np.asarray(dram.next_event(q, b, jnp.int32(t0),
                                        jnp.int32(end), dram=D, policy=POL))
        assert ((ev > t0) & (ev <= end)).all()
        for c in range(D.n_channels):
            span = int(ev[c]) - t0
            probes = {int(ev[c]) - 1, t0 + 1 + int(rng.integers(0, span))}
            for tau in probes:
                if not t0 < tau < int(ev[c]):
                    continue
                q2, b2, st = dram.tick(q, b, jnp.int32(tau), **tick_kw)
                assert int(st.served_rd[c]) == int(st.served_wr[c]) == 0, \
                    (case_seed, c, tau, int(ev[c]))
                for name, x, y in zip(b._fields, b, b2):
                    np.testing.assert_array_equal(
                        np.asarray(x)[c], np.asarray(y)[c],
                        err_msg=f"banks.{name} moved before the horizon "
                                f"(ch {c}, t {tau} < ev {int(ev[c])})")
                for name, x, y in zip(q._fields, q, q2):
                    np.testing.assert_array_equal(
                        np.asarray(x)[c], np.asarray(y)[c],
                        err_msg=f"queue.{name} moved before the horizon "
                                f"(ch {c}, t {tau} < ev {int(ev[c])})")

    prop()


# ---- one-hot match planes against indexed gathers and scatters ------------
# `tick` and `next_event` read per-bank state per queue entry, and write
# the selected command's bank and slot, through one-hot match planes
# (`dram._match`).  These cases hold each form to the indexed
# `take_along_axis` / `.at[]` form it replaces, on random states of
# every preset's geometry and of a two-socket queue.

ONE_HOT_CASES = [(p, q) for p in ("ddr4_2666", "ddr5_4800", "hbm2e")
                 for q in (256, 512)]


def _random_queue_banks(preset, Q, seed):
    """Random (C, Q) slots and (C, RB) bank fields: a third of the slots
    invalid with ``fbank = 0`` (as `init_queue` leaves them), a quarter of
    the banks precharged (``open_row = -1``)."""
    from repro.core.presets import get_preset

    d = get_preset(preset)
    C, RB = d.n_channels, d.banks_per_channel
    rng = np.random.default_rng(seed)
    valid = rng.random((C, Q)) < 2 / 3
    fbank = np.where(valid, rng.integers(0, RB, (C, Q)), 0).astype(np.int32)
    open_row = np.where(rng.random((C, RB)) < 0.25, -1,
                        rng.integers(0, 1 << 16, (C, RB))).astype(np.int32)
    timer = rng.integers(-(1 << 20), 1 << 28, (C, RB)).astype(np.int32)
    return d, rng, valid, fbank, open_row, timer


@pytest.mark.parametrize("preset,Q", ONE_HOT_CASES)
def test_one_hot_bank_reads_equal_take_along_axis(preset, Q):
    d, rng, valid, fbank, open_row, timer = _random_queue_banks(preset, Q, 1)
    C, RB = d.n_channels, d.banks_per_channel
    match = dram._match(jnp.asarray(fbank), RB)
    assert match.shape == (C, RB, Q)
    assert (np.asarray(match).sum(axis=1) == 1).all()   # one bank a slot
    pred = rng.random((C, RB)) < 0.5
    for field in (open_row, timer, pred):
        np.testing.assert_array_equal(
            np.asarray(dram._gather(jnp.asarray(field), match)),
            np.take_along_axis(field, fbank, axis=1))
    # the hit-pending plane: any entry on the bank, over valid slots only
    cond = valid & (rng.random((C, Q)) < 0.3)
    cidx = np.arange(C)
    want = jnp.zeros((C, RB), bool).at[cidx[:, None], fbank].max(cond)
    np.testing.assert_array_equal(
        np.asarray(dram._select(match, jnp.asarray(cond)[:, None, :], 2)),
        np.asarray(want))


@pytest.mark.parametrize("preset,Q", ONE_HOT_CASES)
def test_one_hot_selected_writes_equal_at_set(preset, Q):
    d, rng, valid, fbank, open_row, timer = _random_queue_banks(preset, Q, 2)
    C, RB = d.n_channels, d.banks_per_channel
    cidx = np.arange(C)
    sel = rng.integers(0, Q, C).astype(np.int32)
    sel_match = dram._match(jnp.asarray(sel), Q)
    row = rng.integers(-1, 1 << 16, (C, Q)).astype(np.int32)
    for field in (fbank, row, valid):
        np.testing.assert_array_equal(
            np.asarray(dram._select(sel_match, jnp.asarray(field), 1)),
            field[cidx, sel])
    # selected-bank writes, gated per channel like an ACT/PRE grant
    s_fb = fbank[cidx, sel]
    flag = rng.random(C) < 0.5
    new = rng.integers(0, 1 << 28, C).astype(np.int32)
    bsel = (cidx, s_fb)
    for old in (open_row, timer):
        want = jnp.asarray(old).at[bsel].set(
            jnp.where(flag, jnp.maximum(old[bsel], new), old[bsel]))
        got = jnp.where(dram._match(jnp.asarray(s_fb), RB) & flag[:, None],
                        jnp.maximum(old, new[:, None]), old)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the served-slot plane and the telemetry histogram row
    want = jnp.zeros((C, Q), jnp.int32).at[cidx, sel].set(
        flag.astype(np.int32))
    got = (sel_match & flag[:, None]).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    bucket = rng.integers(0, dram.N_HIST, C).astype(np.int32)
    want = jnp.zeros((C, dram.N_HIST), jnp.int32).at[cidx, bucket].add(
        flag.astype(np.int32))
    got = (dram._match(jnp.asarray(bucket), dram.N_HIST)
           & flag[:, None]).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
