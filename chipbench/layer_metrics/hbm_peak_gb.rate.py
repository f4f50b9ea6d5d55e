"""memory_stats()['peak_bytes_in_use'] on the fullest chip after the window, before the reference runs."""


def read(run):
    b = run.facts.get("hbm_peak_bytes")
    return b / 1e9 if b else None
