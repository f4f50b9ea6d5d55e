"""Mean share, per engine step, of the pool's token places that hold a token of a request in a slot: what the traffic fills of the memory the deployment reserves."""


def read(run):
    xs = run.facts.get("pool_held_share")
    return 100.0 * sum(xs) / len(xs) if xs else None
