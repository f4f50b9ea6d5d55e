"""XLA programs compiled or loaded inside the measured window (jax.monitoring); expect 0."""


def read(run):
    return run.facts.get("compiles_in_window")
