"""Peak bytes in use on the fullest chip, as the device reports them."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e6 if peak else None
