"""Published peaks, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default: a share of a
peak computed against another chip's peak is a wrong number under a true name.
"""

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e' system architecture",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    """The peaks of one chip of ``device_kind``; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; add them to "
            "a benchmark PR's peaks table with their source"
        ) from None
