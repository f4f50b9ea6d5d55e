"""Parameters and optimizer state resident on one chip (parallel.per_device_bytes)."""


def read(run):
    b = run.facts.get("state_bytes_per_chip")
    return b / 1e9 if b else None
