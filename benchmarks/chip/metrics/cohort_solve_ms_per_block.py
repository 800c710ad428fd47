"""cohort_solve_ms_per_block: wall milliseconds of the program's ``solve``
spans (``repro.obs``, host clock) per cohort block of the traced window."""


def read(layer):
    if not layer.get("blocks") or "solve_s" not in layer:
        return None
    return 1e3 * layer["solve_s"] / layer["blocks"]
