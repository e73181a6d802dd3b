"""Address mapping properties (simple + Skylake XOR) and kernel parity."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import addrmap
from repro.core.presets import platform_for

from _proptest import forall, uint32_arrays


@forall(n_cases=30, lines=uint32_arrays(2048))
def test_fields_in_range_simple(lines):
    dec = addrmap.decode(jnp.asarray(lines), "simple")
    assert addrmap.check_fields(dec)


@forall(n_cases=30, lines=uint32_arrays(2048))
def test_fields_in_range_xor(lines):
    dec = addrmap.decode(jnp.asarray(lines), "skylake_xor")
    assert addrmap.check_fields(dec)


def test_mapping_is_deterministic():
    lines = jnp.arange(10000, dtype=jnp.uint32)
    a = addrmap.decode(lines, "skylake_xor")
    b = addrmap.decode(lines, "skylake_xor")
    for f in a._fields:
        assert (np.asarray(getattr(a, f)) == np.asarray(getattr(b, f))).all()


def test_channel_balance():
    """Both mappings must spread a large window uniformly-ish over the
    6 channels (Mess traffic assumes this)."""
    lines = jnp.arange(6 * 4096, dtype=jnp.uint32)
    for mapping in ("simple", "skylake_xor"):
        ch = np.asarray(addrmap.decode(lines, mapping).channel)
        counts = np.bincount(ch, minlength=6)
        assert counts.min() > 0.5 * counts.mean(), (mapping, counts)


def test_xor_scatters_streams_simple_does_not():
    """The paper's Fig. 6a mechanism: a sequential stream stays in one
    row under the simple mapping far longer than under the XOR map."""
    lines = jnp.arange(128, dtype=jnp.uint32) * 6  # one channel, simple
    simple = addrmap.decode(lines, "simple")
    xor = addrmap.decode(lines, "skylake_xor")
    n_banks_simple = len(np.unique(np.asarray(simple.flat_bank)))
    n_banks_xor = len(np.unique(np.asarray(xor.flat_bank)))
    assert n_banks_simple <= 2
    assert n_banks_xor > 4


_BASE = platform_for("ddr4_2666").dram


def _geometry(rng, *, xor_fold: bool):
    """A random synthetic device geometry (encodable when xor_fold)."""
    if xor_fold:
        cb = int(rng.integers(0, 3))
        bb = int(rng.choice([2, 3, 4]))
        lb = int(rng.integers(2, 8 - cb - bb + 1))
        C, B, lpr = 1 << cb, 1 << bb, 1 << lb
        R = int(rng.integers(1, 3))
        rows = 1 << int(rng.integers(9, 15))
    else:
        C = int(rng.integers(1, 9))
        R = int(rng.integers(1, 3))
        B = int(rng.choice([4, 8, 16, 32]))
        lpr = int(rng.choice([16, 32, 64, 128]))
        rows = 1 << int(rng.integers(8, 15))
    return dataclasses.replace(
        _BASE, n_channels=C, ranks_per_channel=R, banks_per_rank=B,
        bank_groups=min(4, B), rows_per_bank=rows,
        cols_per_row=lpr * _BASE.line_bytes // 8)


def _fields(rng, d, n=1024):
    """Random in-range decoded fields for device ``d``."""
    f = lambda hi: rng.integers(0, hi, size=n).astype(np.int32)
    return addrmap.DecodedAddr(
        channel=f(d.n_channels), rank=f(d.ranks_per_channel),
        bank=f(d.banks_per_rank), row=f(d.rows_per_bank),
        col=f(d.lines_per_row))


@forall(n_cases=40, d=lambda rng: _geometry(rng, xor_fold=False),
        lines=uint32_arrays(1024))
def test_encode_simple_round_trips_lines(d, lines):
    """encode(decode(line)) == line for any line within capacity, on
    random geometries (`decode_simple` truncates the row beyond it)."""
    cap = (d.n_channels * d.lines_per_row * d.ranks_per_channel
           * d.banks_per_rank * d.rows_per_bank)
    lines = (lines % min(cap, 1 << 32)).astype(np.uint32)
    dec = addrmap.decode_simple(lines, xp=np, dram=d)
    enc = addrmap.encode_simple(dec, d)
    np.testing.assert_array_equal(enc, lines)


@forall(n_cases=40, case_seed=lambda rng: int(rng.integers(0, 1 << 30)))
def test_encode_round_trips_fields(case_seed):
    """decode(encode(fields)) == fields for in-range fields, both the
    simple packer and the XOR-fold solver, on random geometries."""
    rng = np.random.default_rng(case_seed)
    for xor_fold in (False, True):
        d = _geometry(rng, xor_fold=xor_fold)
        dec = _fields(rng, d)
        if xor_fold:
            assert addrmap.xor_fold_encodable(d) is None
            enc = addrmap.encode_xor_fold(dec, d)
            out = addrmap.decode_xor_fold(enc, d, xp=np)
        else:
            enc = addrmap.encode_simple(dec, d)
            out = addrmap.decode_simple(enc, xp=np, dram=d)
        for name in dec._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out, name)),
                np.asarray(getattr(dec, name)),
                err_msg=f"{'xor_fold' if xor_fold else 'simple'} "
                        f"field {name}")


def test_encode_xor_fold_refuses_real_presets():
    """No shipped preset is XOR-fold-encodable; the solver must say
    why instead of silently mis-encoding."""
    for preset in ("ddr4_2666", "ddr5_4800", "hbm2e"):
        d = platform_for(preset).dram
        reason = addrmap.xor_fold_encodable(d)
        assert isinstance(reason, str) and reason
        with pytest.raises(ValueError, match="not xor_fold-encodable"):
            addrmap.encode_xor_fold(_fields(np.random.default_rng(0), d), d)


def test_kernel_matches_reference():
    from repro.kernels.addr_decode import decode_skylake, decode_reference
    rng = np.random.default_rng(7)
    lines = jnp.asarray(rng.integers(0, 2 ** 32, 5000, dtype=np.uint32))
    d = decode_skylake(lines, interpret=True)
    r = decode_reference(lines)
    for f in d._fields:
        assert (np.asarray(getattr(d, f)) == np.asarray(getattr(r, f))).all()
