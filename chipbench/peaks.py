"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}


class UnknownDevice(KeyError):
    """The benchmark has no peaks for this device kind."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(PEAKS)}") from None
