"""Device mesh construction for one volunteer slice.

Axis convention (outer → inner): ``("dp", "sp", "pp", "ep", "tp")``.

``tp`` is innermost so tensor-parallel collectives (the per-layer
allreduces) land on ICI-adjacent chips; ``dp`` is outermost because its one
gradient reduction per step tolerates the longest hops. ``sp`` (sequence
parallelism's ppermute ring), ``pp`` (pipeline stages' ppermute chain) and
``ep`` (expert parallelism's dispatch/combine all-to-alls) sit between:
they want contiguous neighbours but are far less chatty than tp. Axes of
size 1 cost nothing — every mesh carries all five names so sharding rules
and ``shard_map`` axis references never need to special-case which
strategies are active.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "sp", "pp", "ep", "tp")


def make_mesh(
    dp: int = 1,
    sp: int = 1,
    tp: int = 1,
    pp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``(dp, sp, pp, ep, tp)`` mesh from the first
    dp*sp*pp*ep*tp devices."""
    if devices is None:
        devices = jax.devices()
    need = dp * sp * pp * ep * tp
    if len(devices) < need:
        raise ValueError(
            f"mesh dp={dp} sp={sp} pp={pp} ep={ep} tp={tp} needs {need} "
            f"devices, have {len(devices)}"
        )
    arr = np.asarray(devices[:need]).reshape(dp, sp, pp, ep, tp)
    return Mesh(arr, AXES)


def shard_map_manual(fn, mesh: Mesh, in_specs, out_specs, axis: str):
    """``shard_map`` manual over ONE axis, automatic (GSPMD) over the
    rest."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={axis}, check_vma=False,
    )


def mesh_shape(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def parse_mesh_spec(spec: str) -> dict:
    """Parse a ``"dp=2,tp=2"``-style CLI mesh spec into make_mesh kwargs,
    with errors that name the expected format (a bare int() traceback from
    deep inside volunteer startup helps nobody)."""
    axes: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue  # tolerate a trailing comma
        k, eq, v = part.partition("=")
        k = k.strip()
        if not eq or k not in AXES or not v.strip().isdigit() or int(v) < 1:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected comma-separated axis=N "
                f"with axes from {AXES} and N >= 1 (e.g. 'dp=2,tp=2'); "
                f"offending part: {part!r}"
            )
        axes[k] = int(v)
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}: expected e.g. 'dp=2,tp=2'")
    return axes
