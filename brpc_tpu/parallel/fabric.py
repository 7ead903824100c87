"""Fabric — the peer set of a TPU RPC domain.

The reference identifies peers with ``butil::EndPoint`` (ip:port,
/root/reference/src/butil/endpoint.h:253) resolved through naming services
(/root/reference/src/brpc/policy/*_naming_service.cpp) and pools connections in
a SocketMap.  On TPU the peer set is the XLA device mesh: every chip is
addressed by mesh coordinates, a "connection" is a (mesh, axis) pair whose
links are ICI neighbors, and "name resolution" is mesh construction.  There is
no per-connection state to pool — XLA compiles the routes.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Fabric", "shard_map", "P"]


class Fabric:
    """A device mesh plus helpers to place data and wrap SPMD programs.

    Mirrors the role of brpc's ``NamingService``+``SocketMap`` pair
    (SURVEY.md §2.4): it answers "who are my peers and how do I address
    them", but the answer is mesh axes instead of EndPoint lists.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # -- construction -----------------------------------------------------
    @classmethod
    def auto(
        cls,
        shape: Sequence[int] | None = None,
        axis_names: Sequence[str] = ("link",),
        devices=None,
    ) -> "Fabric":
        """Build a fabric over all (or the given) devices.

        With no shape, lays every device along the last axis — the common
        "one ring" topology used by the echo benchmarks.

        Devices are reshaped in list order, so logical neighbours along
        an axis need not be physical ICI neighbours (a list-order ring
        over a 2x2 host takes diagonal hops).  Results are the same
        either way; hop counts are not.
        """
        devices = list(devices if devices is not None else jax.devices())
        if shape is None:
            shape = [1] * (len(axis_names) - 1) + [len(devices)]
        if math.prod(shape) != len(devices):
            raise ValueError(
                f"mesh shape {tuple(shape)} != device count {len(devices)}"
            )
        dev_array = np.asarray(devices).reshape(shape)
        return cls(Mesh(dev_array, tuple(axis_names)))

    # -- topology ---------------------------------------------------------
    @property
    def axis_names(self):
        return self.mesh.axis_names

    @property
    def size(self) -> int:
        return self.mesh.size

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    # -- placement --------------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def put(self, x, *spec):
        return jax.device_put(x, self.sharding(*spec))

    # -- SPMD wrapping ----------------------------------------------------
    def spmd(self, fn, in_specs, out_specs, check_vma: bool = False):
        """shard_map over this fabric's mesh (the SPMD entry point)."""
        return shard_map(
            fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=check_vma,
        )
