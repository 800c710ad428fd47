"""generator_late_ms_p99: 99th percentile of how late the load generator
sent a request (actual minus scheduled send time) in the traced window.
A late generator is a starved client or a server that is behind."""


def read(layer):
    if "late_ms_p99" not in layer:
        return None
    return layer["late_ms_p99"]
