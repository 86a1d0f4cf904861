"""Production mesh construction (DESIGN.md §7).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; tests and
benches see the default single device).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 chips per pod ("data", "model"); 2 pods adds a leading "pod".

    Under the dry-run's 512 placeholder devices the single-pod mesh takes the
    first 256; on real hardware the defaults resolve to the attached slice.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) > n:
        devices = devices[:n]
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices).reshape(shape), axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small ("data", "model") mesh over whatever devices exist
    (multi-device CPU tests; the sharded-parity test mesh factory).

    ``n`` must divide evenly into ``(n // model, model)`` — the old
    floor-division silently built a mesh over fewer devices than asked
    (n=6, model=4 -> a (1, 4) mesh that dropped 2 devices), which turns a
    topology mistake into a quiet perf bug. Now it raises instead.
    """
    avail = len(jax.devices())
    n = n_devices or avail
    if n < 1 or n > avail:
        raise ValueError(f"make_debug_mesh: n_devices={n} out of range — "
                         f"{avail} device(s) available")
    if n % model != 0:
        raise ValueError(
            f"make_debug_mesh: n_devices={n} is not divisible by "
            f"model={model} — a ({n // model}, {model}) mesh would silently "
            f"drop {n - (n // model) * model} device(s); pick model from the "
            f"divisors of {n} or pass a matching n_devices")
    return jax.make_mesh((n // model, model), ("data", "model"))


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
#: of inter-chip interconnect (4 links of 50 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "int8_ops": 393e12,        # OP/s
        "hbm_bytes": 16e9,         # B
        "hbm_bw": 819e9,           # B/s
        "ici_links": 4,
        "ici_link_bw": 50e9,       # B/s per link
    },
}


def device_peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; an unknown device is an
    error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(DEVICE_PEAKS)}"
                       ) from None
