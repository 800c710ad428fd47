"""train_round_mfu: the least time the chip could take for the W-rounds of
the traced window (their required FLOPs and bytes at the published peaks,
``work.py``), as a share of the window's wall time."""


def read(layer):
    if not layer.get("rounds") or not layer.get("window_s"):
        return None
    return 100.0 * layer["min_s"] / layer["window_s"]
