"""Published peaks of the chips the benchmark may run on, keyed by the exact
`device_kind` JAX reports. The benchmark's own copy: the yardstick must not
move when the program's table (`observability.metrics.DEVICE_PEAKS`) does.

Source of the one row: Google Cloud documentation, "TPU v5e" system
architecture page (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip), as
quoted in /opt/skills/guides/on-chip-measurement/SKILL.md section 4.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    """The row for `device_kind`; an unknown kind is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks/lib/peaks.py has no row for device_kind "
            f"{device_kind!r}; known: {sorted(PEAKS)}") from None
