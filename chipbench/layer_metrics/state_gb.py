"""The engine's `tpu_dist_serve_state_bytes` gauge: the per-slot recurrent state resident beside the weights and the paged pool, all slots, allocated at start."""


def read(run):
    try:
        from tpu_dist.observe.registry import REGISTRY
    except ImportError:
        return None
    for line in REGISTRY.render().splitlines():
        if line.startswith("tpu_dist_serve_state_bytes "):
            return float(line.split()[1]) / 1e9
    return None
