"""Production meshes (DESIGN.md §4).

Defined as FUNCTIONS so importing this module never touches jax device
state: a single pod is a 16×16 = 256-chip ("data", "model") mesh; the
multi-pod proof mesh is 2×16×16 = 512 chips with a leading "pod" axis (data
parallelism across pods — gradient all-reduce crosses the DCI).
"""

from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (opting out of
    sharding-in-types)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_stream_mesh(n_devices: int | None = None, axis: str = "data"):
    """Flat 1-D mesh over the local devices — the shape sharded streaming
    wants (``StreamEngine(mesh=...)``): rows partition over one axis, and
    the bucket ladder pads row counts to a multiple of its size.  On a
    CPU host, force more virtual devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (the multi-device CI job does exactly this)."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh((n,), (axis,))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def axis_rules(multi_pod: bool = False, layout: str = "tp") -> dict:
    """Logical→mesh axis mapping installed before tracing.

    Layouts (the §Perf hillclimb lever — the physical mesh never changes):
      tp      — batch over data axes, tensor/sequence/expert over "model".
      dp      — pure data parallel: batch over EVERY axis, weights
                replicated (the right shape for sub-1B models where TP
                collectives dwarf compute).
      tp_nosp — tensor parallel without sequence-parallel resharding.
    """
    pods = ("pod",) if multi_pod else ()
    if layout == "hybrid":
        # manual data parallelism (shard_map) — batch locality is implicit
        # inside the manual region, so "dp" must not appear in constraints.
        return {"dp": None, "tp": "model", "sp": "model", "ep": "model"}
    if layout == "dp":
        return {
            "dp": pods + ("data", "model"),
            "tp": None, "sp": None, "ep": None,
        }
    if layout == "tp_nosp":
        return {
            "dp": pods + ("data",),
            "tp": "model", "sp": None, "ep": "model",
        }
    return {
        "dp": pods + ("data",),
        "tp": "model",
        "sp": "model",  # sequence-parallel residual stream
        "ep": "model",  # expert parallelism shares the model axis
    }
