"""Multi-device sharding of the platform's batch axes.

`run_frontend` is one compiled program per static stage configuration;
its batch axis — Mess pace points or stacked application traces — is
embarrassingly parallel.  `sharded_vmap` maps that axis across every
available accelerator with `jax.shard_map` (data-parallel, no
cross-shard communication) and degenerates to a plain `jax.vmap` on a
single device, so a CPU run, one TPU chip and a multi-chip host run the
same call sites.

Because the mapped function is elementwise along the batch axis (no
collectives, no cross-batch reductions), the sharded result is
**bit-identical** to the single-device vmap result — asserted on forced
CPU devices by tests/test_sharding_sweeps.py and on four TPU chips by
``python chip_smoke.py --chips 4``.  The same property is why the
varying-manual-axes check is off (``check_vma=False``): no value ever
crosses shards, so there is nothing for it to guard.

Batch sizes that do not divide the device count are right-padded by
repeating the last element; `sharded_vmap` slices the padding off the
outputs, so callers never see it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

BATCH_AXIS = "batch"


def device_count() -> int:
    """Devices the sweep axes shard across (1 = plain vmap fallback)."""
    return jax.device_count()


def _pad_batch(tree, pad: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.repeat(a[-1:], pad, axis=0)], axis=0),
        tree)


def _unpad_batch(tree, n: int):
    return jax.tree_util.tree_map(lambda a: a[:n], tree)


def shard_mapped(fn, mesh: Mesh):
    """``vmap(fn)`` with its leading axis split over ``mesh``'s batch axis.

    The un-jitted program `sharded_vmap` runs on more than one device;
    the batch length must be a multiple of the mesh size.
    """
    spec = PartitionSpec(BATCH_AXIS)
    # check_vma=False: a `lax.scan` carry that starts unvarying comes
    # out varying over `batch`, which the check rejects; fn has no
    # collectives, so per-shard values never need the check
    return jax.shard_map(jax.vmap(fn), mesh=mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)


def sharded_vmap(fn, n_devices: int | None = None, donate: bool = False):
    """``vmap(fn)`` over the leading axis, sharded across devices.

    Args:
        fn: a function of one batched pytree argument; must be
            elementwise along the leading (batch) axis.
        n_devices: devices to shard over; defaults to all available.
            With one device this is exactly ``jax.vmap(fn)`` (no mesh,
            no padding) — the CPU fallback path.
        donate: donate the batched input buffers to the computation
            (``jax.jit(..., donate_argnums=0)``): XLA may alias them
            into outputs/scratch instead of holding a live copy per
            point, cutting per-point device copies and peak memory on
            large sweep batches.  The caller's input arrays are
            **consumed** — only pass ``True`` for buffers that are
            rebuilt per call (see `repro.core.mess.sweep`) or
            explicitly handed over (`repro.traces.replay`'s
            ``donate=`` entry points).
    Returns:
        A jitted function ``batched(tree) -> tree_out`` whose leading
        output axis matches the input batch length.  Results are
        bit-identical to the single-device vmap path.
    """
    nd = n_devices or device_count()
    if nd > device_count():
        raise ValueError(f"n_devices={nd} exceeds the "
                         f"{device_count()} available devices")
    dn = (0,) if donate else ()
    if nd <= 1:
        return jax.jit(jax.vmap(fn), donate_argnums=dn)

    mapped = shard_mapped(fn, Mesh(jax.devices()[:nd], (BATCH_AXIS,)))
    jitted = jax.jit(mapped, donate_argnums=dn)

    @functools.wraps(fn)
    def batched(tree):
        n = jax.tree_util.tree_leaves(tree)[0].shape[0]
        pad = (-n) % nd
        out = jitted(_pad_batch(tree, pad) if pad else tree)
        return _unpad_batch(out, n) if pad else out

    return batched
