"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window."""


def read(params, observed, traced, peaks):
    return observed.get("memory_peak_bytes")
