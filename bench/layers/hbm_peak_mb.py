"""Peak device memory of the run, `memory_stats()["peak_bytes_in_use"]` of
the fullest chip, in MB."""


def read(run):
    peak = run["out"]["device"].get("memory_peak_bytes")
    return peak / 1e6 if peak else None
