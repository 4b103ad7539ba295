"""Parallel environment: mesh construction + process bootstrap.

Role parity: reference comm bootstrap — c_gen_nccl_id's TCP id-exchange +
c_comm_init's ring setup (operators/collective/) and the Gloo rendezvous
in fleet RoleMaker (role_maker.py:172).  TPU-native: one process per
HOST drives all its local chips; `jax.distributed.initialize` is the
rendezvous (coordinator address from the launcher's env), and a
`jax.sharding.Mesh` over all devices replaces every ring.  Collectives
ride ICI within a slice and DCN across hosts, scheduled by XLA.

Env contract (same names the reference launcher exports, SURVEY §2.9):
  PADDLE_TRAINER_ID        process (host) index
  PADDLE_TRAINERS_NUM      number of processes
  PADDLE_COORDINATOR       coordinator ip:port (ours; reference derives it
                           from PADDLE_TRAINER_ENDPOINTS[0])
  PADDLE_TRAINER_ENDPOINTS comma list, used as coordinator fallback
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

_mesh = None
_ring_axes: Dict[int, object] = {}


def init_parallel_env(mesh_shape: Optional[Sequence[int]] = None,
                      axis_names: Optional[Sequence[str]] = None):
    """Bootstrap multi-process (if env says so) and build the global mesh.

    Single process: mesh over all visible devices.  Multi process: after
    jax.distributed.initialize, jax.devices() spans all hosts.
    """
    import jax

    global _mesh
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    if nproc > 1 and not _distributed_initialized():
        coord = os.environ.get("PADDLE_COORDINATOR")
        if not coord:
            eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
            coord = eps.split(",")[0] if eps else None
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
        # cross-process CPU collectives (the localhost-cluster tests) ride
        # jax's default jax_cpu_collectives_implementation, gloo
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=rank)

    devices = jax.devices()
    if mesh_shape is None:
        from ..framework import flags as _flags

        pp = int(_flags.flag("pp_degree") or 0)
        ep = int(_flags.flag("ep_degree") or 0)
        if (pp > 1 or ep > 1) and axis_names is None:
            # FLAGS_pp_degree / FLAGS_ep_degree: carve a (dp, pp),
            # (dp, ep), or (dp, ep, pp) mesh out of the visible devices
            # so stage/expert-annotated programs run without an
            # explicit mesh_shape.  The degree a program runs with is
            # ALWAYS the mesh axis size; these defaults only shape
            # meshes built fully shapeless — an EXPLICIT axis_names
            # argument wins over the flags (the caller named its axes
            # for a reason).  Bad factorizations are rejected HERE,
            # with the axis named, instead of deep in GSPMD with an
            # opaque sharding error.
            carve = 1
            for name, deg in (("ep", ep), ("pp", pp)):
                if deg <= 1:
                    continue
                if len(devices) % deg != 0:
                    raise ValueError(
                        f"FLAGS_{name}_degree={deg} does not divide "
                        f"the {len(devices)} visible devices; pass an "
                        f"explicit mesh_shape or fix the flag")
                carve *= deg
            if carve > len(devices):
                raise ValueError(
                    f"FLAGS_ep_degree={ep} x FLAGS_pp_degree={pp} = "
                    f"{carve} exceeds the {len(devices)} visible "
                    f"devices ('ep' x 'pp' must fit the mesh); pass "
                    f"an explicit mesh_shape or fix the flags")
            if len(devices) % carve != 0:
                raise ValueError(
                    f"FLAGS_ep_degree={ep} x FLAGS_pp_degree={pp} = "
                    f"{carve} does not divide the {len(devices)} "
                    f"visible devices; pass an explicit mesh_shape or "
                    f"fix the flags")
            mesh_shape = [len(devices) // carve]
            axis_names = ["dp"]
            if ep > 1:
                mesh_shape.append(ep)
                axis_names.append("ep")
            if pp > 1:
                mesh_shape.append(pp)
                axis_names.append("pp")
            axis_names = tuple(axis_names)
        else:
            mesh_shape = [len(devices)]
            axis_names = tuple(axis_names or ("dp",))[:1] or ("dp",)
    elif axis_names is None:
        axis_names = ("dp",)
    import numpy as np

    n = int(np.prod(mesh_shape))
    if n != len(devices):
        raise ValueError(
            f"mesh shape {tuple(mesh_shape)} needs {n} devices, "
            f"have {len(devices)}")
    dev_array = np.asarray(devices).reshape(mesh_shape)
    _mesh = jax.sharding.Mesh(dev_array, tuple(axis_names))
    return _mesh


def _distributed_initialized() -> bool:
    # must NOT call jax.process_count(): that instantiates the XLA
    # backend, after which jax.distributed.initialize refuses to run
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception:
        return False


def get_mesh():
    return _mesh


def set_mesh(mesh, ring_axes: Optional[Dict[int, object]] = None):
    global _mesh, _ring_axes
    _mesh = mesh
    if ring_axes is not None:
        _ring_axes = dict(ring_axes)
    return _mesh


def reset_mesh():
    global _mesh, _ring_axes
    _mesh = None
    _ring_axes = {}


def ring_axes() -> Dict[int, object]:
    return dict(_ring_axes)


def get_world_size() -> int:
    """Data-parallel world size (reference nranks): size of the dp axis,
    else the whole mesh, else 1."""
    if _mesh is None:
        return 1
    if "dp" in _mesh.axis_names:
        return int(_mesh.shape["dp"])
    return _mesh.size


def get_rank() -> int:
    # host-level rank (reference trainer_id is per device; on TPU the
    # process drives all local devices, so rank == process index)
    rid = os.environ.get("PADDLE_TRAINER_ID")
    if rid not in (None, ""):
        return int(rid)
    try:
        import jax

        return int(jax.process_index())
    except ImportError:  # pragma: no cover
        return 0


class ParallelEnv:
    """Reference fluid.dygraph.ParallelEnv parity."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return max(get_world_size(),
                   int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1))

    @property
    def device_id(self):
        return 0

    local_rank = rank
    nranks = world_size
