"""Calibrated per-device machine model (ROADMAP direction 4).

``cost_model.Hardware`` ships TPU-v5e *constants*; on any other silicon
those are guesses, so ``combo_lower_bound`` sits far below every real
score and prunes little.  This module measures what THIS host can
actually do — a matmul ladder per dtype (achievable peak FLOP/s), an
HBM/stream bandwidth probe, and collective latency/bandwidth points per
(mesh shape, collective kind) — and persists the result as a versioned
:class:`MachineProfile` in the ``machine_cache`` table beside
``score_cache``.

Resolution happens *at the scorer*, exactly like executor cache tags:
the process that scores a job (tuner parent, scoring server) calibrates
or loads its own host's profile and views it as a
:class:`~repro.core.cost_model.Hardware` via
:func:`hardware_from_profile`, with the built-in constants as the
fallback for anything unmeasured.  The view's ``name`` embeds the
profile content hash, so ``DryRunExecutor.cache_tag``
(``dryrun:<hw.name>``) automatically isolates calibrated scores from
constant-model scores — and two hosts with identical profiles share
cache rows.

Soundness contract: calibration can never break pruning exactness.  The
lower bound and the scorer divide by the *same* executor ``hw``
(``analyze_compiled`` uses ``executor.hw``), so rescaling the constants
rescales bound and score together and ``bound <= score`` is preserved
under any profile.  What calibration changes is *which term dominates*
— e.g. on CPU the measured FLOP/s is ~3 orders below v5e while
bandwidth is ~1.5 orders below, so the (tight) compute floor dominates
the score and the bound prunes far harder.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import logging
import time
from dataclasses import dataclass, field, replace
from typing import Dict, Optional

from repro.core.cost_model import Hardware, V5E

log = logging.getLogger("repro.machine")

#: bump on any change to what the microbenchmarks measure or how the
#: profile is keyed — old rows can then never alias new semantics.
PROFILE_VERSION = 1

#: matmul ladder sizes (square, per dtype); tiny = smoke/CI sizes.
_MATMUL_SIZES = (512, 1024, 2048)
_MATMUL_SIZES_TINY = (128, 256)
#: stream probe array bytes.
_STREAM_BYTES = 1 << 26          # 64 MiB
_STREAM_BYTES_TINY = 1 << 22     # 4 MiB
#: per-shard bytes for collective probes.
_COLL_BYTES = 1 << 22
_COLL_BYTES_TINY = 1 << 18
_DTYPES = ("bfloat16", "float32")


def profile_key(platform: str, device_kind: str, n_devices: int) -> str:
    """Versioned machine identity — the ``machine_cache`` primary key."""
    return f"machine:v{PROFILE_VERSION}:{platform}:{device_kind}:{n_devices}"


@dataclass(frozen=True)
class MachineProfile:
    """Measured capabilities of one host's devices.

    ``peak_flops`` maps dtype name -> achieved FLOP/s per device;
    ``hbm_bw`` is achieved stream bytes/s per device; ``collectives``
    maps ``"<kind>:<axis>=<size>:<shard_bytes>"`` -> {"s", "bytes",
    "bytes_s"} where ``bytes`` follows the analyzer's ring conventions
    (all-reduce = 2*r*(n-1)/n per device), so ``bytes_s`` is directly
    comparable to ``Hardware.link_bw``.
    """
    platform: str
    device_kind: str
    n_devices: int
    peak_flops: Dict[str, float] = field(default_factory=dict)
    hbm_bw: float = 0.0
    collectives: Dict[str, Dict[str, float]] = field(default_factory=dict)
    meta: Dict[str, object] = field(default_factory=dict)
    version: int = PROFILE_VERSION

    @property
    def key(self) -> str:
        return profile_key(self.platform, self.device_kind, self.n_devices)

    @property
    def pid(self) -> str:
        """Content hash: equal measurements -> equal id, on any host."""
        return hashlib.sha1(
            json.dumps(self.to_json(), sort_keys=True).encode()).hexdigest()

    def to_json(self) -> Dict:
        return {"platform": self.platform, "device_kind": self.device_kind,
                "n_devices": self.n_devices,
                "peak_flops": dict(self.peak_flops), "hbm_bw": self.hbm_bw,
                "collectives": {k: dict(v)
                                for k, v in self.collectives.items()},
                "meta": dict(self.meta), "version": self.version}

    @classmethod
    def from_json(cls, d: Dict) -> "MachineProfile":
        return cls(platform=d["platform"], device_kind=d["device_kind"],
                   n_devices=int(d["n_devices"]),
                   peak_flops={k: float(v)
                               for k, v in (d.get("peak_flops") or {}).items()},
                   hbm_bw=float(d.get("hbm_bw") or 0.0),
                   collectives={k: {kk: float(vv) for kk, vv in v.items()}
                                for k, v in (d.get("collectives") or {}).items()},
                   meta=dict(d.get("meta") or {}),
                   version=int(d.get("version", 0)))

    def best_link_bw(self) -> float:
        """Best measured collective bytes/s (0.0 when single-device)."""
        return max((v.get("bytes_s", 0.0)
                    for v in self.collectives.values()), default=0.0)


# --- microbenchmarks ---------------------------------------------------------

def _time_best(fn, *args, repeats: int = 3) -> float:
    """Best-of-N wall time of an already-jitted fn (first call warms)."""
    import jax
    jax.block_until_ready(fn(*args))            # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _matmul_peak(dtype: str, sizes, repeats: int) -> float:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: a @ b)
    best = 0.0
    for n in sizes:
        try:
            x = jnp.ones((n, n), dtype=dtype)
            y = jnp.ones((n, n), dtype=dtype)
            t = _time_best(f, x, y, repeats=repeats)
        except Exception as e:           # dtype unsupported on this backend
            log.debug("matmul probe %s n=%d failed: %s", dtype, n, e)
            continue
        if t > 0:
            best = max(best, 2.0 * n ** 3 / t)
    return best


def _stream_bw(nbytes: int, repeats: int) -> float:
    import jax
    import jax.numpy as jnp
    n = max(1, nbytes // 4)
    x = jnp.ones((n,), dtype="float32")
    # scale+shift defeats copy-elision; traffic = read + write
    f = jax.jit(lambda a: a * 1.000001 + 0.5)
    t = _time_best(f, x, repeats=repeats)
    return 2.0 * x.nbytes / t if t > 0 else 0.0


def _collective_points(n_devices: int, shard_bytes: int,
                       repeats: int) -> Dict[str, Dict[str, float]]:
    """All-reduce / all-gather over a flat ring of all local devices.

    Bytes use the analyzer's ring conventions (``runtime.hlo``):
    all-reduce moves ``2*r*(n-1)/n`` per device, all-gather
    ``r*(n-1)/n`` — so the derived ``bytes_s`` lands in the same units
    as ``Hardware.link_bw`` and the scorer's ``collective_s``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.meshspec import MeshSpec

    out: Dict[str, Dict[str, float]] = {}
    if n_devices < 2:
        return out
    mesh = MeshSpec.of(data=n_devices).to_mesh()
    rows = max(1, shard_bytes // 4)
    x = jax.device_put(
        jnp.ones((rows * n_devices,), dtype="float32"),
        jax.sharding.NamedSharding(mesh, P("data")))
    r = rows * 4                                     # shard bytes per device
    probes = {
        "all_reduce": (lambda a: jax.lax.psum(a, "data"),
                       P("data"), P(), 2.0 * r * (n_devices - 1) / n_devices),
        "all_gather": (lambda a: jax.lax.all_gather(a, "data", tiled=True),
                       P("data"), P(), 1.0 * r * (n_devices - 1) / n_devices),
    }
    for kind, (body, in_spec, out_spec, conv_bytes) in probes.items():
        try:
            f = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                              out_specs=out_spec, check_vma=False)
            t = _time_best(jax.jit(f), x, repeats=repeats)
        except Exception as e:
            log.debug("collective probe %s failed: %s", kind, e)
            continue
        if t > 0:
            out[f"{kind}:data={n_devices}:{r}"] = {
                "s": t, "bytes": conv_bytes, "bytes_s": conv_bytes / t}
    return out


def calibrate(tiny: bool = False, repeats: int = 3) -> MachineProfile:
    """Run the microbenchmark suite on this host's default backend."""
    import jax
    devs = jax.devices()
    platform = jax.default_backend()
    device_kind = getattr(devs[0], "device_kind", "") or platform
    n = len(devs)
    t0 = time.perf_counter()
    sizes = _MATMUL_SIZES_TINY if tiny else _MATMUL_SIZES
    peaks = {dt: _matmul_peak(dt, sizes, repeats) for dt in _DTYPES}
    peaks = {k: v for k, v in peaks.items() if v > 0}
    bw = _stream_bw(_STREAM_BYTES_TINY if tiny else _STREAM_BYTES, repeats)
    coll = _collective_points(
        n, _COLL_BYTES_TINY if tiny else _COLL_BYTES, repeats)
    prof = MachineProfile(
        platform=platform, device_kind=device_kind, n_devices=n,
        peak_flops=peaks, hbm_bw=bw, collectives=coll,
        meta={"tiny": bool(tiny), "repeats": int(repeats),
              "calibrated_s": round(time.perf_counter() - t0, 3),
              "matmul_sizes": list(sizes)})
    log.info("calibrated %s: peak=%s hbm_bw=%.3g coll=%d pts (%.1fs)",
             prof.key, {k: f"{v:.3g}" for k, v in peaks.items()}, bw,
             len(coll), prof.meta["calibrated_s"])
    return prof


def load_or_calibrate(db, tiny: bool = False,
                      force: bool = False) -> MachineProfile:
    """Resolve this host's profile against ``db.machine_cache``.

    Version-mismatched or unreadable rows are recalibrated, never
    trusted — same policy as versioned executor cache tags.
    """
    import jax
    devs = jax.devices()
    key = profile_key(jax.default_backend(),
                      getattr(devs[0], "device_kind", "")
                      or jax.default_backend(), len(devs))
    if not force:
        row = db.machine_get(key)
        if row is not None:
            try:
                prof = MachineProfile.from_json(row)
                if prof.version == PROFILE_VERSION and prof.key == key:
                    return prof
            except (KeyError, TypeError, ValueError):
                pass
            log.warning("stale/corrupt machine profile %s: recalibrating", key)
    prof = calibrate(tiny=tiny)
    db.machine_put(prof.key, prof.pid, prof.to_json())
    return prof


def hardware_from_profile(profile: MachineProfile,
                          base: Hardware = V5E) -> Hardware:
    """View a profile as the scorer's ``Hardware``; unmeasured fields
    fall back to ``base``'s constants.

    ``peak_flops`` takes the best dtype on the ladder (achievable peak,
    matching the constant's bf16 meaning); ``link_bw`` takes the best
    measured collective point.  The name embeds the profile hash so
    ``DryRunExecutor.cache_tag`` keys calibrated scores separately per
    profile content.
    """
    peak = max(profile.peak_flops.values(), default=0.0)
    link = profile.best_link_bw()
    return replace(
        base,
        name=f"cal{PROFILE_VERSION}-{profile.platform}-{profile.pid[:8]}",
        peak_flops=peak or base.peak_flops,
        hbm_bw=profile.hbm_bw or base.hbm_bw,
        link_bw=link or base.link_bw)


def resolve_machine(machine, db) -> Optional[Hardware]:
    """Tuner/server-facing resolution of a ``machine=`` argument.

    ``None`` -> None (keep the constant model); ``"auto"`` ->
    load-or-calibrate against ``db`` (tiny ladder: the sweep should not
    stall minutes on first contact — run ``calibrate()`` offline for a
    full ladder); a :class:`MachineProfile` -> its Hardware view; a
    :class:`Hardware` -> itself.
    """
    if machine is None:
        return None
    if isinstance(machine, Hardware):
        return machine
    if isinstance(machine, MachineProfile):
        return hardware_from_profile(machine)
    if machine == "auto":
        return hardware_from_profile(load_or_calibrate(db, tiny=True))
    raise ValueError(f"machine must be None, 'auto', a MachineProfile or "
                     f"a Hardware; got {machine!r}")


def main(argv=None) -> int:
    """CLI: calibrate this host and persist/print the profile (CI smoke)."""
    ap = argparse.ArgumentParser(description="machine calibration")
    ap.add_argument("--db", default="", help="sweep DB path (persist here)")
    ap.add_argument("--tiny", action="store_true", help="smoke-size ladder")
    ap.add_argument("--force", action="store_true", help="recalibrate even "
                    "if a cached profile exists")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    if args.db:
        from repro.core.db import SweepDB
        db = SweepDB(args.db)
        prof = load_or_calibrate(db, tiny=args.tiny, force=args.force)
    else:
        prof = calibrate(tiny=args.tiny, repeats=args.repeats)
    print(json.dumps({"key": prof.key, "pid": prof.pid,
                      **prof.to_json()}, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
