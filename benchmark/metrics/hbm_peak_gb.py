"""``hbm_peak_gb`` (GB): ``memory_stats()["peak_bytes_in_use"]`` after
the window, on the fullest device. Layer: device. No bound: at a tenth of
the HBM a few percent more memory costs a user nothing."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak is not None else None
