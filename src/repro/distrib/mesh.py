"""ExecMesh: the one device-placement abstraction of the distributed runtime.

The driver used to branch between two parallel renderings of every step
and chunk function — a vmapped single-device emulation and a
``shard_map`` multi-device path — that had to be kept numerically in
lockstep by hand.  :class:`ExecMesh` collapses the branching: it names a
placement (``ndev`` devices x ``per`` chips per device over one
``chips`` mesh axis) and exposes exactly the collective vocabulary the
distributed superstep needs (``axis_index`` / ``psum`` / ``pmax`` /
``all_gather`` / ``gather_records``) plus a ``shard_jit`` wrapper.

On a single device every helper degenerates to the identity / local
reduction (``axis_index`` is 0, ``per == num_chips``, gathers are
no-ops), so ONE step function written against the mesh reproduces the
old vmapped emulation *bitwise* — the exchanged records flatten to the
exact same scatter indices — while the same function under a real
multi-device mesh runs the collective path.  Single-device meshes are
traceable outside ``shard_map`` (no collectives appear), which is what
lets the analysis passes abstract-trace the distributed chunk function.

Placement is chosen by :meth:`ExecMesh.build`: any ``ndev`` that divides
the chip count works, and when the host's device count does not divide
it the mesh falls back to the largest dividing device subset with a
warning instead of failing (the old driver raised a hard ``ValueError``).
Force real CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before JAX
is imported — see ``tests/_subproc.py``).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from ..core import collectives


def largest_dividing_devices(num_chips: int, device_count: int) -> int:
    """The largest ``ndev <= device_count`` with ``num_chips % ndev == 0``
    (>= 1 always: one device trivially divides any chip count)."""
    ndev = max(1, min(int(device_count), int(num_chips)))
    while num_chips % ndev:
        ndev -= 1
    return ndev


@dataclasses.dataclass(frozen=True)
class ExecMesh:
    """A ``num_chips = ndev * per`` placement over one mesh axis."""

    num_chips: int
    ndev: int
    axis: str = "chips"

    def __post_init__(self):
        if self.ndev < 1 or self.num_chips % self.ndev:
            raise ValueError(
                f"{self.ndev} devices do not divide {self.num_chips} chips")

    # ------------------------------------------------------------ geometry
    @property
    def per(self) -> int:
        """Chips per device (the vmapped width inside each shard)."""
        return self.num_chips // self.ndev

    @property
    def is_sharded(self) -> bool:
        return self.ndev > 1

    @property
    def backend_name(self) -> str:
        """The driver's historical backend label for this placement."""
        return "shard_map" if self.is_sharded else "vmap"

    # ------------------------------------------------------------- factory
    @classmethod
    def build(cls, num_chips: int, backend: str = "auto",
              device_count: int | None = None) -> "ExecMesh":
        """Choose a placement for ``num_chips`` chips.

        ``backend``: 'auto' (multi-device when more than one device can
        divide the chips), 'vmap' (force single-device emulation) or
        'shard_map' (request multi-device; falls back gracefully).  When
        ``device_count`` (default ``jax.device_count()``) does not divide
        the chip count, the mesh uses the largest dividing subset and
        warns — it never raises.
        """
        if backend not in ("auto", "vmap", "shard_map"):
            raise ValueError(f"unknown distributed backend {backend!r}")
        dc = jax.device_count() if device_count is None else int(device_count)
        if backend == "vmap" or num_chips == 1:
            return cls(num_chips, 1)
        ndev = largest_dividing_devices(num_chips, dc)
        if backend == "shard_map" and ndev < dc:
            warnings.warn(
                f"{num_chips} chips do not divide {dc} devices; falling "
                f"back to the largest dividing subset ({ndev} device"
                f"{'s' if ndev != 1 else ''}, {num_chips // ndev} chips "
                f"per device)", RuntimeWarning, stacklevel=2)
        if backend == "auto" and ndev == 1:
            return cls(num_chips, 1)
        return cls(num_chips, ndev)

    # ----------------------------------------- in-region collective helpers
    # Each is the identity / a local reduction on a single-device mesh, so
    # the step function stays traceable outside shard_map there.
    def axis_index(self):
        if not self.is_sharded:
            return jnp.int32(0)
        return jax.lax.axis_index(self.axis)

    def chip_ids(self):
        """Global chip ids of this device's ``per`` chips."""
        return (self.axis_index() * self.per
                + jnp.arange(self.per, dtype=jnp.int32))

    def psum(self, x):
        return jax.lax.psum(x, self.axis) if self.is_sharded else x

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis) if self.is_sharded else x

    def all_gather(self, x):
        """Tiled all-gather along the mesh axis (identity on one device:
        the stacked array already holds every chip)."""
        if not self.is_sharded:
            return x
        return jax.lax.all_gather(x, self.axis, tiled=True)

    def gather_records(self, parts):
        """Exchange compact per-device record buffers: every device ends
        up holding the full ``(num_chips * R,)`` record stream in chip
        order (see ``collectives.gather_records``)."""
        if not self.is_sharded:
            return parts
        return collectives.gather_records(parts, self.axis)

    # ----------------------------------------------------------- jit wrapper
    def shard_jit(self, fn, in_specs, out_specs):
        """``jax.jit(fn)`` on one device; ``jit(shard_map(fn, ...))`` on a
        real mesh.  ``in_specs`` / ``out_specs`` are pytrees of booleans
        (prefix trees allowed, like shard_map's): True = partitioned
        along the chips axis, False = replicated.
        """
        if not self.is_sharded:
            return jax.jit(fn)
        from jax.sharding import PartitionSpec as P

        def conv(tree):
            return jax.tree.map(lambda b: P(self.axis) if b else P(), tree)

        return jax.jit(jax.shard_map(fn, mesh=self.jax_mesh(),
                                     in_specs=conv(in_specs),
                                     out_specs=conv(out_specs),
                                     check_vma=False))

    def jax_mesh(self):
        """The ``jax.sharding.Mesh`` of this placement (the first ``ndev``
        devices, one ``Auto``-typed axis)."""
        return jax.make_mesh((self.ndev,), (self.axis,),
                             axis_types=(jax.sharding.AxisType.Auto,))
