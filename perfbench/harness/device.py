"""The device as JAX reports it. Runs before anything touches the program;
a platform other than the one the configuration names ends the run with no
result line. (Logic copied from chip_smoke.py's require_tpu / peak_hbm.)"""

from __future__ import annotations

from .cells import BenchError


def require_platform(platform: str, chips: int) -> dict:
    try:
        import jax
        devs = jax.devices()
    except Exception as exc:  # JAX raises RuntimeError subclasses of its own
        raise BenchError(f"JAX found no usable device "
                         f"({type(exc).__name__}: {exc})")
    found = devs[0].platform
    if found != platform:
        raise BenchError(f"JAX is running on platform {found!r} "
                         f"({devs[0].device_kind}, {len(devs)} device(s)), "
                         f"not {platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), "
                         f"JAX reports {len(devs)}")
    return {"platform": found, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak on the fullest chip; None where the backend reports nothing
    (XLA:CPU in the tests)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
