"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table, so that no change to the program can
move the yardstick a roofline share is read against.  A device that is not
in the table is an error, never a default.  The compute peak is the bf16
rate for every operand dtype: a v5e publishes no f32 rate, and an f32
contraction at ``Precision.HIGHEST`` runs as several bf16 passes of the MXU.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,   # bf16 FLOP/s
        "peak_bw": 819e9,       # HBM bytes/s
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
