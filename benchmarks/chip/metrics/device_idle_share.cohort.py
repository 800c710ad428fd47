"""device_idle_share.cohort: share of the traced window in which no
operation ran on the device, in the cohort cells."""


def read(layer):
    if not layer.get("window_s") or not layer.get("blocks"):
        return None
    return 100.0 * (1.0 - layer["busy_s"] / layer["window_s"])
