"""Mesh construction for the production pod(s) and the paper-faithful
3-level topo mesh.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before the first jax call).

Axis-to-bandwidth-tier mapping (DESIGN.md §2):

  production mesh (16, 16) ("data", "model"):
      "model"  — the intra tier (short ICI paths): weight + gradient shards
      "data"   — the inter tier: optimizer sharding + replica sync
  multi-pod (2, 16, 16) ("pod", "data", "model"): "pod" is DCI (slowest) and
      joins the inter tier (deeper optimizer sharding, batch replicated).

  topo mesh (data, repl, node, gcd) = (16, 2, 4, 2): the paper's 3 levels —
      "gcd" (2)        = the MI250X GCD pair       -> primary weight shards
      "node"x"gcd" (8) = the Frontier node         -> gradient shards + secondary
      "data"x"repl"    = inter-node                -> optimizer shards

  device mesh (data, node, gcd) over the live devices: 1 chip -> (1, 1, 1),
      a 2x2 host -> (1, 2, 2) with each gcd pair two ICI neighbours, 8 (fake
      CPU) devices -> (2, 2, 2), the test mesh's shape.
"""
from __future__ import annotations

from ..compat import make_mesh as _mk


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_topo_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 2, 4, 2) if multi_pod else (16, 2, 4, 2)
    axes = (("pod",) if multi_pod else ()) + ("data", "repl", "node", "gcd")
    return _mk(shape, axes if multi_pod else ("data", "repl", "node", "gcd"))


def make_device_mesh(devices=None):
    """(data, node, gcd) mesh over ``devices`` (default: ``jax.devices()``).

    ``gcd`` and ``node`` take a factor of 2 each where the count allows,
    the rest goes to ``data``. Devices are ordered process-major, then by
    their torus coordinates with x fastest, so on a TPU host each gcd pair
    is two chips joined by a direct ICI link and ``zero_tiers`` maps it to
    l0."""
    import jax
    devs = list(jax.devices() if devices is None else devices)
    n = len(devs)
    gcd = 2 if n % 2 == 0 else 1
    node = 2 if n % 4 == 0 else 1
    devs.sort(key=lambda d: (d.process_index,
                             tuple(reversed(getattr(d, "coords", ()))), d.id))
    return _mk((n // (node * gcd), node, gcd), ("data", "node", "gcd"),
               devices=devs)


def make_test_mesh(shape=(2, 2, 2), axes=("data", "node", "gcd")):
    """Small fake-device mesh for CPU tests (8 devices)."""
    return _mk(shape, axes)


def process_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that cross a process boundary.

    ``jax.devices()`` is process-major, so with the leading axes sized to a
    multiple of the process count these are exactly the leading (inter) axes;
    any other arrangement means intra-tier collectives would go over the
    slowest links, which ``zero_tiers`` rejects.
    """
    import numpy as np
    devs = np.asarray(mesh.devices)
    pidx = np.reshape([getattr(d, "process_index", 0)
                       for d in devs.ravel()], devs.shape)
    if (pidx == pidx.flat[0]).all():
        return ()
    spanning = []
    for k, name in enumerate(mesh.axis_names):
        first = np.take(pidx, [0], axis=k)
        if not (pidx == first).all():
            spanning.append(name)
    return tuple(spanning)


def zero_tiers(mesh) -> dict[str, tuple[str, ...]]:
    """Map a mesh's axes onto the (l0, intra, inter) bandwidth tiers.

    On a multi-process mesh the process boundary MUST fall inside the inter
    tier: the primary weight gather and the secondary partition live on the
    intra axes precisely because those are the fast in-node links, and a
    process boundary there would silently run them over the network.
    """
    names = set(mesh.axis_names)
    if {"node", "gcd"} <= names:
        intra = ("node", "gcd")
        l0 = ("gcd",)
    elif "model" in names:
        intra = ("model",)
        l0 = ("model",)
    else:  # single-axis test meshes
        intra = (mesh.axis_names[-1],)
        l0 = intra
    inter = tuple(a for a in mesh.axis_names if a not in intra)
    crossing = tuple(a for a in process_axes(mesh) if a not in inter)
    if crossing:
        raise ValueError(
            f"process boundary crosses intra-tier axes {crossing} of mesh "
            f"{dict(mesh.shape)}: a multi-process launch must keep whole "
            f"intra groups (axes {intra}) inside one process — lower the "
            f"per-process device count or reorder the mesh so only the "
            f"leading axes {inter} span processes")
    return dict(l0=l0, intra=intra, inter=inter)


def scheme_config(scheme: str, mesh, *, psi=None, n_layers=None,
                  memory_budget=None, **over):
    """Build the ZeroConfig for `scheme` on `mesh`.

    ``scheme="auto"`` runs the topology-aware planner (repro.topo) against
    the live mesh and returns its top-ranked config; ``psi``/``n_layers``
    describe the workload (defaulting to the paper's 20B/44-layer model) and
    ``memory_budget`` bounds per-device state bytes. Any remaining keyword
    overrides (quant_block, overlap, compute_dtype, ...) apply to the chosen
    config exactly as they would to a preset.
    """
    if scheme == "auto":
        import dataclasses

        from ..topo import plan_for_mesh
        # stream_grads changes the pricing regime (overlappable grad RS,
        # os-layout grad memory), not just the engine: hand it to the
        # planner so the budget search admits what streaming actually fits
        stream = bool(over.pop("stream_grads", False))
        cfg = plan_for_mesh(mesh, psi=psi, n_layers=n_layers,
                            memory_budget=memory_budget,
                            stream_grads=stream, top_k=1)[0].cfg
        return dataclasses.replace(cfg, **over) if over else cfg
    from ..core.partition import preset
    tiers = zero_tiers(mesh)
    return preset(scheme, intra_axes=tiers["intra"], inter_axes=tiers["inter"],
                  l0_axes=tiers["l0"], axis_sizes=dict(mesh.shape), **over)
