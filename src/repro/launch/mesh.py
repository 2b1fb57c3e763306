"""Production mesh construction + axis environments + FSDP spec widening.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; batch shards over
(pod, data), parameters/experts/heads over model, FSDP over data.

K-search meshes: ``make_wave_mesh`` carves the visible devices into the
2-D ``(lane, data)`` mesh the sharded wavefront planes consume, and
``SubmeshPool`` leases per-worker submeshes to the threaded distributed-fit
executor (each worker keeps ONE submesh for its lifetime — submeshes are
a worker-identity resource, not a function of the k being evaluated).

Functions (not module constants) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import threading
from typing import Any, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.layers import Axes

PyTree = Any


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_wave_mesh(
    lanes: int | None = None, data: int = 1, devices: Sequence[Any] | None = None
) -> Mesh:
    """2-D ``(lane, data)`` mesh for the sharded wavefront planes.

    ``lanes`` parallel k-fits, each distributed over ``data`` devices
    (pyDNMFk psum structure) — lanes × data devices total. With
    ``lanes=None`` every remaining device becomes a lane
    (``len(devices) // data``). Raises if the device count doesn't factor.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if data < 1:
        raise ValueError(f"data must be >= 1, got {data}")
    if lanes is None:
        if len(devs) % data:
            raise ValueError(f"{len(devs)} devices do not split into data={data} shards")
        lanes = len(devs) // data
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    need = lanes * data
    if need > len(devs):
        raise ValueError(f"mesh ({lanes} lanes x {data} data) needs {need} devices, "
                         f"have {len(devs)}")
    return jax.make_mesh(
        (lanes, data), ("lane", "data"), (AxisType.Auto, AxisType.Auto), devices=devs[:need]
    )


class SubmeshPool:
    """Lease one submesh per *worker* for the threaded distributed-fit path.

    The executor's workers are threads that each run one k-evaluation at a
    time on a dedicated device group; the evaluate closure only sees the k,
    so the pool keys the lease on ``threading.get_ident()``. First touch
    assigns the next free submesh round-robin; every later call from the
    same worker returns the same submesh. (Keying on k instead — e.g.
    ``submeshes[k % n]`` — lands two concurrent workers on the same device
    group whenever their ks collide mod n, serializing the fits the
    submeshes exist to parallelize.)
    """

    def __init__(self, submeshes: Sequence[Mesh]):
        if not submeshes:
            raise ValueError("SubmeshPool needs at least one submesh")
        self.submeshes = list(submeshes)
        self._lock = threading.Lock()
        self._assign: dict[int, Mesh] = {}

    def acquire(self) -> Mesh:
        """The calling worker's submesh (assigned on first touch)."""
        ident = threading.get_ident()
        with self._lock:
            mesh = self._assign.get(ident)
            if mesh is None:
                mesh = self.submeshes[len(self._assign) % len(self.submeshes)]
                self._assign[ident] = mesh
            return mesh

    def assignments(self) -> dict[int, int]:
        """thread ident -> submesh index (introspection for tests/traces)."""
        with self._lock:
            index = {id(m): i for i, m in enumerate(self.submeshes)}
            return {t: index[id(m)] for t, m in self._assign.items()}


def make_axes(mesh: Mesh, global_batch: int | None = None) -> Axes:
    """Axis environment for a mesh; drops batch sharding when the global
    batch can't shard evenly (long_500k's batch=1)."""
    names = mesh.axis_names
    batch_axes = tuple(n for n in ("pod", "data") if n in names)
    if global_batch is not None:
        dp = 1
        for n in batch_axes:
            dp *= mesh.shape[n]
        if global_batch % dp != 0:
            batch_axes = ()
    return Axes(batch=batch_axes, model="model", model_size=mesh.shape["model"])


def dp_size(mesh: Mesh) -> int:
    dp = 1
    for n in ("pod", "data"):
        if n in mesh.axis_names:
            dp *= mesh.shape[n]
    return dp


def apply_fsdp(
    specs: PyTree, shapes: PyTree, fsdp_axis: str = "data", fsdp_size: int = 16,
    min_elems: int = 1 << 22,
) -> PyTree:
    """Widen param specs with FSDP sharding over `fsdp_axis`.

    For every leaf >= min_elems whose spec has a None entry on a dim
    divisible by fsdp_size, shard that dim over the fsdp axis. This is the
    MaxText-style fsdp+tensor hybrid: without it, llama3-405b's bf16 params
    are 50 GB/device (model-axis only); with it they are 3.2 GB/device.
    """

    def widen(spec: P, shaped) -> P:
        shape = shaped.shape
        if len(shape) != len(spec):
            # stacked-segment leading dim etc. — pad spec view
            return spec
        n = 1
        for s in shape:
            n *= s
        if n < min_elems:
            return spec
        entries = list(spec)
        # prefer widening the largest eligible dim (least padding waste);
        # never shard the leading layer-stack dim of scanned params (>=3D)
        start = 1 if len(shape) >= 3 else 0
        order = sorted(range(start, len(shape)), key=lambda i: -shape[i])
        for i in order:
            if entries[i] is None and shape[i] % fsdp_size == 0:
                entries[i] = fsdp_axis
                return P(*entries)
        return spec

    return jax.tree.map(widen, specs, shapes, is_leaf=lambda s: isinstance(s, P))


def named(mesh: Mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda s: isinstance(s, P)
    )
