"""Boot-time mesh probe: measure what the interconnect actually delivers.

ZeRO++'s knobs (qwZ/qgZ block sizes, hpZ placement, prefetch-ring depth)
only pay off when the tier bandwidths justify them, and those bandwidths
are a property of the *deployment*, not the code — frontier practice picks
them from measurements, not defaults.  This module is the measurement:

  * :func:`probe_mesh` times small REAL collectives per mesh axis (an
    all-gather and an all-to-all at 2-3 sizes each) on the live mesh and
    fits a per-tier ``t = latency + bytes / bandwidth`` model.
  * :func:`static_profile` loads the committed ``profiles/static_v5e.json``
    instead of timing — the deterministic ``--tune=static`` mode CI uses
    (timing on shared CI hosts is noise; the resolver must be reproducible).

The fitted :class:`ProbeProfile` is the only input the resolver
(``repro.tune.resolve``) accepts for interconnect numbers: nothing else in
the repo hard-codes a bandwidth into a *decision* (the analytic benchmark
constants remain as defaults for the paper-figure projections).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, Optional, Sequence, Tuple

_PROFILE_DIR = os.path.join(os.path.dirname(__file__), "profiles")
STATIC_PROFILE_PATH = os.path.join(_PROFILE_DIR, "static_v5e.json")

# Fit clamps: a probe on simulated host devices can produce degenerate
# timings (zero-variance, negative slope); the resolver must still get a
# usable positive model out.
_MIN_BW = 1e6      # 1 MB/s floor
_MAX_BW = 1e15     # effectively-free tier (degenerate size-1 axes)


@dataclasses.dataclass(frozen=True)
class TierProfile:
    """Fitted alpha/beta collective cost model for one mesh-axis tier."""

    latency_s: float       # alpha: fixed per-collective cost
    bandwidth_Bps: float   # 1/beta: per-device wire bytes per second

    def time_s(self, wire_bytes: float) -> float:
        return self.latency_s + wire_bytes / self.bandwidth_Bps


@dataclasses.dataclass(frozen=True)
class ProbeProfile:
    """Per-tier collective cost model for one mesh.

    ``tiers`` maps each mesh axis name to its fitted :class:`TierProfile`.
    ``source`` records provenance ("probe" = timed on the live mesh,
    "static" = the committed CI profile) so resolved policies are
    self-describing.
    """

    source: str
    mesh_axes: Tuple[str, ...]
    mesh_shape: Tuple[int, ...]
    tiers: Dict[str, TierProfile]

    # -- resolver-facing queries -------------------------------------------
    def fast_bw(self, intra_axis: str = "model") -> float:
        """Bandwidth of the fast (intra) tier."""
        t = self.tiers.get(intra_axis)
        return t.bandwidth_Bps if t else _MAX_BW

    def slow_bw(self, inter_axes: Sequence[str] = ()) -> float:
        """Bandwidth of the slowest tier a collective over ``inter_axes``
        touches (the bottleneck link); all tiers when axes are omitted."""
        axes = tuple(inter_axes) or tuple(self.tiers)
        bws = [self.tiers[a].bandwidth_Bps for a in axes if a in self.tiers]
        return min(bws) if bws else _MAX_BW

    def coll_latency(self, axes: Sequence[str] = ()) -> float:
        """Per-collective fixed cost over ``axes`` (worst tier)."""
        names = tuple(axes) or tuple(self.tiers)
        lats = [self.tiers[a].latency_s for a in names if a in self.tiers]
        return max(lats) if lats else 0.0

    # -- (de)serialization --------------------------------------------------
    def to_json(self) -> Dict:
        return {
            "source": self.source,
            "mesh_axes": list(self.mesh_axes),
            "mesh_shape": list(self.mesh_shape),
            "tiers": {a: {"latency_s": t.latency_s,
                          "bandwidth_Bps": t.bandwidth_Bps}
                      for a, t in self.tiers.items()},
        }

    @classmethod
    def from_json(cls, d: Dict) -> "ProbeProfile":
        return cls(
            source=d["source"],
            mesh_axes=tuple(d["mesh_axes"]),
            mesh_shape=tuple(d["mesh_shape"]),
            tiers={a: TierProfile(float(t["latency_s"]),
                                  float(t["bandwidth_Bps"]))
                   for a, t in d["tiers"].items()},
        )

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "ProbeProfile":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def for_mesh(self, mesh_axes: Sequence[str],
                 mesh_shape: Sequence[int]) -> "ProbeProfile":
        """Re-key this profile onto another mesh's axes.

        Axes present in ``tiers`` keep their numbers; unknown axis names
        fall back to the 'data' tier (the mid interconnect) so a test mesh
        with exotic axis names still resolves.  Size-1 axes carry no
        traffic and get the free tier.
        """
        fallback = self.tiers.get("data") or next(iter(self.tiers.values()))
        tiers = {}
        for a, g in zip(mesh_axes, mesh_shape):
            if g <= 1:
                tiers[a] = TierProfile(0.0, _MAX_BW)
            else:
                tiers[a] = self.tiers.get(a, fallback)
        return ProbeProfile(self.source, tuple(mesh_axes), tuple(mesh_shape),
                            tiers)


def static_profile(mesh_axes: Sequence[str] = ("pod", "data", "model"),
                   mesh_shape: Optional[Sequence[int]] = None,
                   path: str = STATIC_PROFILE_PATH) -> ProbeProfile:
    """The committed deterministic profile, re-keyed for ``mesh_axes``."""
    base = ProbeProfile.load(path)
    if mesh_shape is None:
        # unknown sizes: assume every named axis is populated (size 2 is
        # enough to keep it off the free tier)
        mesh_shape = tuple(2 for _ in mesh_axes)
    return base.for_mesh(tuple(mesh_axes), tuple(mesh_shape))


# ---------------------------------------------------------------------------
# live probe
# ---------------------------------------------------------------------------

def _fit(points: Sequence[Tuple[float, float]]) -> Tuple[float, float]:
    """Least-squares ``t = alpha + bytes/bw`` over (wire_bytes, seconds)."""
    n = len(points)
    mx = sum(p[0] for p in points) / n
    mt = sum(p[1] for p in points) / n
    var = sum((x - mx) ** 2 for x, _ in points)
    slope = (sum((x - mx) * (t - mt) for x, t in points) / var) if var else 0.0
    slope = max(slope, 1.0 / _MAX_BW)
    alpha = max(mt - slope * mx, 0.0)
    bw = min(max(1.0 / slope, _MIN_BW), _MAX_BW)
    return alpha, bw


def _time_collective(mesh, axis: str, n_local: int, iters: int,
                     kind: str) -> float:
    """Best-of-``iters`` wall time of one small collective over ``axis``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P


    g = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    if kind == "gather":
        def body(x):
            return lax.all_gather(x, axis, tiled=True)
        x = jnp.ones((g * n_local,), jnp.bfloat16)
        in_specs, out_specs = P(axis), P()
    else:  # all_to_all: local (g, n_local) block, same wire volume as gather
        def body(x):
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
        x = jnp.ones((g * g, n_local), jnp.bfloat16)
        in_specs, out_specs = P(axis), P(axis)
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs))
    jax.block_until_ready(fn(x))   # compile + warm up outside the clock
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    return best


def probe_mesh(mesh, sizes: Sequence[int] = (1 << 13, 1 << 15, 1 << 17),
               iters: int = 2) -> ProbeProfile:
    """Time small real collectives per mesh axis and fit per-tier costs.

    For every axis of size > 1 this times an all-gather and an all-to-all
    at each of ``sizes`` local elements (bf16) and least-squares-fits
    ``t = latency + wire_bytes / bandwidth``.  Size-1 axes carry no
    traffic and get the free tier.  Cheap by construction: the largest
    default message is 256 KiB per device.
    """
    names = tuple(mesh.axis_names)
    shape = tuple(int(s) for s in mesh.devices.shape)
    tiers: Dict[str, TierProfile] = {}
    for axis, g in zip(names, shape):
        if g <= 1:
            tiers[axis] = TierProfile(0.0, _MAX_BW)
            continue
        pts = []
        for n_local in sizes:
            wire = 2.0 * n_local * (g - 1)   # bf16, per device, both kinds
            pts.append((wire, _time_collective(mesh, axis, n_local, iters,
                                               "gather")))
            pts.append((wire, _time_collective(mesh, axis, n_local, iters,
                                               "a2a")))
        alpha, bw = _fit(pts)
        tiers[axis] = TierProfile(alpha, bw)
    return ProbeProfile("probe", names, shape, tiers)
