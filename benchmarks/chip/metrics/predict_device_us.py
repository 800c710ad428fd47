"""predict_device_us: device microseconds of the serving lookup program
(``_margins``) per request of the traced window."""


def read(layer):
    if not layer.get("requests") or not layer.get("margins_s"):
        return None
    return 1e6 * layer["margins_s"] / layer["requests"]
