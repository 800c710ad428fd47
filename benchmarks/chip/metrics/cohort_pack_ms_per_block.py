"""cohort_pack_ms_per_block: wall milliseconds of the program's ``pack``
spans (``repro.obs``, host clock) per cohort block of the traced window."""


def read(layer):
    if not layer.get("blocks") or "pack_s" not in layer:
        return None
    return 1e3 * layer["pack_s"] / layer["blocks"]
