"""Peak bytes in use on the fullest chip, read after the window and before the
reference touches the device again (``memory_stats()["peak_bytes_in_use"]``)."""


def compute(run):
    peak = run.notes.get("program_peak_bytes") or run.memory_peak()
    return peak / 1e9 if peak else None
