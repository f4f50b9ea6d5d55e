"""Share of the first chip's device time spent in all-gather, reduce-scatter, all-reduce and permute operations over the traced slice."""


def read(run):
    if run.trace is None or run.trace["chips"] < 2:
        return None
    return run.trace["collective_share_pct"]
