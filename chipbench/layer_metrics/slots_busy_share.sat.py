"""Mean share of the decode slots that were active, per engine step."""


def read(run):
    xs = run.facts.get("slots_busy_share")
    return 100.0 * sum(xs) / len(xs) if xs else None
