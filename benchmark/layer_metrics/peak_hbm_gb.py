"""device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip, the
process's high-water mark, in GB (1e9). A guard: memory traded for speed
shows here."""


def read(run):
    peak = run["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
