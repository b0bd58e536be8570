"""Shared device-mesh construction — the one place meshes are built.

Reference analogue: the NCCL ring/hierarchical setup
(``platform/nccl_helper.h:246`` InitHierarchicalCtxs) chose which GPUs form
which rings; on TPU the equivalent decision is how logical mesh axes map
onto the physical ICI torus.  ``jax.experimental.mesh_utils.
create_device_mesh`` knows the slice topology (v4/v5 3-D tori) and lays the
trailing mesh axes along the fastest-wraparound dimensions, so e.g. an
``mp`` axis lands on adjacent chips and ``dp`` collectives ride full rings
— a flat ``Mesh(np.array(devices).reshape(...))`` instead gives whatever
enumeration order happens to be, which on a v5e-256 puts model-parallel
neighbours hops apart.

Multi-host with data-center network (DCN) between slices: the 'dcn' axis
goes OUTERMOST (``create_hybrid_device_mesh``), so only the outer
collective crosses DCN.

Device order is made deterministic (process_index, device id) before any
layout decision — under ``jax.distributed`` every process must build the
identical mesh.
"""

import numpy as np
import jax
from jax.sharding import Mesh


def shard_map(f, mesh, in_specs, out_specs, check_vma=None,
              axis_names=None):
    """``jax.shard_map`` — the ONE call every shard_map site routes
    through; ``check_vma`` / ``axis_names`` left at None keep jax's
    defaults."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def ordered_devices(platform=None, devices=None):
    """All visible devices of ``platform`` in deterministic order."""
    if devices is None:
        devices = jax.devices(platform) if platform else jax.devices()
    return sorted(devices, key=lambda d: (d.process_index, d.id))


def local_devices(platform=None):
    """THIS process's devices of ``platform`` (id order).  Under
    ``jax.distributed``, ``jax.devices()`` is the GLOBAL list —
    anything that PLACES data or queries a concrete device
    (``device_put`` targets, memory stats, Place construction) must
    pick from here; only mesh construction spans the global list.
    Falls back to the global list when the filter would be empty (a
    platform whose devices all live elsewhere — caller's error surfaces
    at use)."""
    devs = jax.devices(platform) if platform else jax.devices()
    mine = [d for d in devs if d.process_index == jax.process_index()]
    return sorted(mine, key=lambda d: d.id) or devs


def build_mesh(axis_names, axis_sizes=None, devices=None, platform=None):
    """Build a ``jax.sharding.Mesh`` with topology-aware device layout.

    axis_names: tuple of mesh axis names, e.g. ("dp", "mp").
    axis_sizes: matching sizes; a single -1 (or None entry) is inferred
        from the device count.  Defaults to all devices on one axis.
    devices: explicit device list (tests, subsets); default all of
        ``platform``.

    On TPU the layout goes through ``mesh_utils.create_device_mesh`` so
    mesh axes follow the ICI torus; for 'dcn' as the FIRST axis on a
    multi-slice/multi-host job, ``create_hybrid_device_mesh`` places it
    across slices.  CPU (virtual) and single-device meshes use C-order
    reshape — there is no topology to exploit.
    """
    axis_names = tuple(axis_names)
    devices = ordered_devices(platform, devices)
    n = len(devices)
    if axis_sizes is None:
        axis_sizes = (n,) if len(axis_names) == 1 else None
    if axis_sizes is None:
        raise ValueError("axis_sizes required for multi-axis meshes")
    sizes = list(axis_sizes)
    unknown = [i for i, s in enumerate(sizes) if s in (-1, None)]
    if len(unknown) > 1:
        raise ValueError("at most one axis size may be -1")
    known = int(np.prod([s for s in sizes if s not in (-1, None)]))
    if unknown:
        if known == 0 or n % known:
            raise ValueError("cannot infer axis %r: %d devices / %s"
                             % (axis_names[unknown[0]], n, sizes))
        sizes[unknown[0]] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(
            "mesh %s=%s needs %d devices, have %d"
            % (axis_names, tuple(sizes), int(np.prod(sizes)), n))

    if axis_names[0] == "dcn" and sizes[0] > 1 and \
            (not devices or devices[0].platform != "tpu"):
        # non-TPU pod (multi-process CPU CI, GPU hosts): 'dcn' must land
        # on process boundaries — ordered_devices groups by
        # process_index, so a C-order reshape puts whole process
        # granules into each dcn row EXACTLY when the row size divides
        # the per-process device count layout.  Validate instead of
        # silently building a mesh whose "cross-node" axis cuts through
        # a node (collectives would cross DCN on the wrong axis).
        _check_dcn_granules(devices, sizes[0], axis_names)

    if devices and devices[0].platform == "tpu":
        # a layout the topology cannot hold raises: on one host that means
        # the mesh request was wrong, and enumeration order would hide it
        from jax.experimental import mesh_utils as jmu
        n_slices = len({d.process_index for d in devices})
        if axis_names[0] == "dcn" and n_slices > 1 and sizes[0] > 1:
            # process_is_granule: 'dcn' means node/process boundary
            # here (the hierarchical-allreduce contract), not TPU
            # slice boundary — a multi-host single-slice pod still
            # groups by host
            # same-rank contract: per-axis within-granule sizes x
            # across-granule sizes; 'dcn' spans granules, the rest
            # live inside one
            arr = jmu.create_hybrid_device_mesh(
                (1,) + tuple(sizes[1:]),
                (sizes[0],) + (1,) * (len(sizes) - 1),
                devices=devices, process_is_granule=True)
            arr = arr.reshape(sizes)
        else:
            arr = jmu.create_device_mesh(tuple(sizes), devices=devices)
    else:
        arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names)


def _check_dcn_granules(devices, dcn_size, axis_names):
    """Validate that a leading 'dcn' axis of size ``dcn_size`` maps onto
    whole process granules under the C-order reshape of the
    (process_index, id)-ordered device list: every dcn row must hold
    devices of a contiguous, non-straddling process group.  Single-
    process device sets pass trivially (a virtual 'dcn' axis on one
    host is layout-only)."""
    n_procs = len({d.process_index for d in devices})
    if n_procs <= 1:
        return
    inner = len(devices) // dcn_size
    for row in range(dcn_size):
        procs = {d.process_index
                 for d in devices[row * inner:(row + 1) * inner]}
        for other in range(dcn_size):
            if other == row:
                continue
            op = {d.process_index
                  for d in devices[other * inner:(other + 1) * inner]}
            if procs & op:
                raise ValueError(
                    "mesh %s: 'dcn' size %d does not align with the %d "
                    "process granules (%d devices) — a process's devices "
                    "would straddle the cross-node axis; use a dcn size "
                    "that divides evenly into whole processes"
                    % (axis_names, dcn_size, n_procs, len(devices)))


def global_dp_mesh(platform=None):
    """One-axis 'dp' mesh over the GLOBAL device list (all processes) —
    the pod-scale data-parallel default (fluid.distributed.init +
    docs/distributed.md).  Every process builds the identical mesh."""
    return build_mesh(("dp",), platform=platform)
