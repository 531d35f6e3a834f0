"""The chip a run measures: found, named, and held to its published peaks.

A run that finds no TPU, or fewer chips than its cell asks for, fails; a
device kind missing from `PEAKS` is an error, never a default.
"""

from __future__ import annotations

# device_kind -> published peaks of ONE chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture: 16 GB HBM2 at 819 GB/s, 197 TFLOP/s bf16)",
    },
}


class NoChip(RuntimeError):
    pass


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to hkvbench/device.py with their source")
    return PEAKS[kind]


def require(chips: int):
    """The first `chips` TPU devices; raises NoChip otherwise."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is "
                     f"{devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} TPU chips, found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def describe(devs) -> dict:
    import jax

    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory(devs, key: str) -> int:
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devs)
