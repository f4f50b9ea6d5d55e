"""What a builder tells the trace of a program that XLA partitions.

`parallel.partitioned_over` makes a `Partitioned` and the builder traces
the model inside it; `nn.dot_product_attention` and `TransformerLM.apply`
ask `partitioned()` for the one they are traced under.  The context mesh
(`ops.kernel_for_platform`'s test, and Mosaic's) says THAT the compiler
partitions the program; this says by which axes, which no trace can see.
"""

import contextlib
import contextvars
import dataclasses
import math

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_dist.ops import _partitioned_by_compiler

_PARTITIONED = contextvars.ContextVar("tpu_dist_partitioned", default=None)


@dataclasses.dataclass
class Partitioned:
    """What a builder says with `parallel.partitioned_over`: the mesh XLA
    partitions its program over (the context mesh says as much), and what
    no trace can see, which of its axes split an activation's leading
    (batch) dimension and which its heads.  It also keeps what attention
    traced under it became (`nn.dot_product_attention`), for the builder
    to report."""

    mesh: jax.sharding.AbstractMesh
    batch_axes: tuple[str, ...] = ()
    head_axes: tuple[str, ...] = ()
    # one entry a call of `nn.dot_product_attention` that flash takes:
    # (form, the axes it is split over, one device's shape)
    attention: list = dataclasses.field(default_factory=list)
    # times the per-device function's Python body ran (was traced)
    per_device_traces: int = 0
    _entered: list = dataclasses.field(default_factory=list, repr=False)

    def __enter__(self):
        if not self._entered:  # a new trace: what is kept describes the last
            self.attention.clear()
            self.per_device_traces = 0
        # the context mesh is what `kernel_for_platform` and Mosaic read
        with contextlib.ExitStack() as stack:
            token = _PARTITIONED.set(self)
            stack.callback(_PARTITIONED.reset, token)
            stack.enter_context(jax.sharding.use_abstract_mesh(self.mesh))
            self._entered.append(stack.pop_all())
        return self

    def __exit__(self, *exc):
        return self._entered.pop().__exit__(*exc)

    def attention_spec(self, shape) -> P | None:
        """For attention over ``(batch, heads, S, d)`` that flash takes:
        batch and heads over the axes that shard them, the spec of a
        `shard_map` in which one device holds its share whole; None where
        this trace is not the compiler's to partition, an axis does not
        divide its dimension, or an axis of several devices splits
        neither (it would stay the compiler's inside the `shard_map`,
        and Mosaic refuses that too).  Notes what the call becomes."""
        mesh = jax.sharding.get_abstract_mesh()
        sizes = self.mesh.shape
        batch = tuple(a for a in self.batch_axes if sizes[a] > 1)
        heads = tuple(a for a in self.head_axes if sizes[a] > 1)
        split = tuple(math.prod(sizes[a] for a in axes) for axes in (batch, heads))
        if (
            mesh != self.mesh
            or mesh.size == 1
            or len(shape) != 4
            or set(batch) | set(heads) != {a for a in sizes if sizes[a] > 1}
            or shape[0] % split[0]
            or shape[1] % split[1]
        ):
            form = "dense" if _partitioned_by_compiler() else "flash"
            self.attention.append((form, P(), tuple(shape)))
            return None
        spec = P(batch or None, heads or None)
        self.attention.append((
            "flash", spec,
            (shape[0] // split[0], shape[1] // split[1], *shape[2:]),
        ))
        return spec

    def pin_to_batch(self, x):
        """``x`` held on the axes that split the batch, wherever the
        compiler is free to lay it out otherwise."""
        if (
            not self.batch_axes
            or self.mesh.size == 1
            or jax.sharding.get_abstract_mesh() != self.mesh
        ):
            return x
        return lax.with_sharding_constraint(x, P(self.batch_axes))


def partitioned() -> Partitioned | None:
    """The `parallel.partitioned_over` this trace runs under, if any."""
    return _PARTITIONED.get()
