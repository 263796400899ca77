"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.
The benchmark's own copy: a PR may edit the program's table.  A device
that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
