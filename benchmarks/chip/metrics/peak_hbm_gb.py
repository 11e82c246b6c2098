"""Peak device memory in use (``peak_bytes_in_use`` of the fullest chip,
read after the window) in GB: what decides whether a model fits."""


def read(run):
    return None if run.memory_peak_bytes is None else run.memory_peak_bytes / 1e9
