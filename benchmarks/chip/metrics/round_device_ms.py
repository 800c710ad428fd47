"""round_device_ms: device milliseconds of the scanned W-round program
(``_scan_rounds``) per W-round of the traced window."""


def read(layer):
    if not layer.get("rounds") or not layer.get("scan_rounds_s"):
        return None
    return 1e3 * layer["scan_rounds_s"] / layer["rounds"]
