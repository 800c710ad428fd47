"""device_idle_share.train: share of the traced window in which no
operation ran on the device, in the training cells."""


def read(layer):
    if not layer.get("window_s") or layer.get("rounds") is None:
        return None
    return 100.0 * (1.0 - layer["busy_s"] / layer["window_s"])
