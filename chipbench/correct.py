"""What decides `correct`: the timed path's own output against the plain
reference, number by number, each printed beside its limit.

Training: the losses of the first steps, the first gradient's norms (read
from the optimizer's state after one step) and the parameters' change,
the norms compared by the worst leaf.  Serving: over a seeded sample of
the requests the window served, the widest gap by which a served token's
logit lies below the reference's best at its position.  ``control=True``
also puts the reference itself in the program's place one precision step
down (matrix products rounded through float8_e4m3fn, the step below
bfloat16) and prints what it reads: those readings, not a guess, are what
the limits in the configuration files were set from (PERF.md §2).

``family`` is the module of the configuration's family
(`chipbench/families/<name>.py`): its seeded weights and its plain
reference are all this file knows of an architecture.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.harness import log, seed_key

CONTROL_DTYPE = jnp.float8_e4m3fn


@dataclass
class Compared:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclass
class Verdict:
    ok: bool
    rows: list
    control_ok: bool | None = None   # None: the control was not read
    control_rows: list | None = None


def report(rows: list[Compared], *, label: str = "correct") -> bool:
    ok = True
    for r in rows:
        ok &= r.ok
        print(
            f"chipbench {label}: {r.name} = {r.value:.6g}  limit {r.limit:.6g}  "
            f"{'ok' if r.ok else 'OVER'}", file=sys.stderr,
        )
    return ok


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Largest |program norm - reference norm| over the leaves, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    ref_all = np.concatenate([np.ravel(np.asarray(v)) for v in reference.values()])
    floor = float(np.median(ref_all))
    worst, where = 0.0, ""
    for k, ref in reference.items():
        ref = np.ravel(np.asarray(ref, np.float64))
        got = np.ravel(np.asarray(program[k], np.float64))
        gap = np.abs(got - ref) / np.maximum(ref, floor)
        i = int(np.argmax(gap))
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{k}[{i}]"
    return worst, where


# ------------------------------------------------------------------ training


def shard_last_divisible(mesh_devices):
    """Shardings that spread the reference's float32 state over the cell's
    chips (one chip: nothing to do).  Each leaf is split along its last
    axis that the chip count divides; the batch block along its rows."""
    n = len(mesh_devices)
    if n == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(mesh_devices), ("x",))

    def leaf(a):
        for ax in range(a.ndim - 1, -1, -1):
            if a.shape[ax] % n == 0:
                return NamedSharding(mesh, P(*([None] * ax + ["x"])))
        return NamedSharding(mesh, P())

    return mesh, leaf, NamedSharding(mesh, P(None, "x"))


def reference_training(family, cfg: dict, seed: int, batches: list[np.ndarray], *, lr: float,
                       block_rows: int, devices, quant=None) -> dict:
    """The reference through ``len(batches)`` steps from the seeded
    weights: each step's loss, the first gradient's leaf norms, and the
    leaf norms of the parameters' change after the last step."""
    ref = family.reference
    sh = shard_last_divisible(devices)
    p0 = family.make_init(cfg, "float32", layout="reference")(seed_key(seed))
    if sh is not None:
        _, leaf, rows_sh = sh
        p0 = jax.tree.map(lambda a: jax.device_put(a, leaf(a)), p0)
    else:
        p0 = jax.device_put(p0, devices[0])

    def step(p, st, rows):
        loss, g = ref.loss_and_grad(p, rows, cfg, quant=quant)
        if sh is not None:
            g = jax.tree.map(lambda g_, p_: jax.lax.with_sharding_constraint(g_, p_.sharding), g, p0)
        p2, st2 = ref.adam_update(p, g, st, lr=lr)
        return p2, st2, loss, ref.leaf_norms(g)

    step = jax.jit(step, donate_argnums=(1,))
    delta = jax.jit(lambda a, b: ref.leaf_norms(jax.tree.map(jnp.subtract, a, b)))
    # the moments are born where their parameters live: left to itself a
    # jitted zeros_like lands whole on the first chip
    p_sh = jax.tree.map(lambda a: a.sharding, p0)
    st_sh = {"t": None, "m": p_sh, "v": p_sh}
    p, st = p0, jax.jit(ref.adam_init, out_shardings=st_sh)(p0)
    losses, gnorms = [], None
    for rows in batches:
        blocks = np.asarray(rows).reshape(-1, block_rows, rows.shape[-1])
        blocks = jax.device_put(blocks, rows_sh if sh is not None else devices[0])
        p, st, loss, gn = step(p, st, blocks)
        losses.append(float(loss))
        if gnorms is None:
            gnorms = jax.tree.map(np.asarray, gn)
    return {
        "losses": losses, "grad_norms": gnorms,
        "delta_norms": jax.tree.map(np.asarray, delta(p, p0)),
    }


def compare_training(program: dict, ref: dict, limits: dict, prefix: str = "") -> list[Compared]:
    # one limit for every step's loss, or a list: one a step, its last for
    # the later steps (the steps after an update are noisier, PERF.md section 2)
    loss = limits["loss_gap"] if isinstance(limits["loss_gap"], list) else [limits["loss_gap"]]
    rows = [
        Compared(f"{prefix}loss_gap_step{i + 1}", abs(a - b), loss[min(i, len(loss) - 1)])
        for i, (a, b) in enumerate(zip(program["losses"], ref["losses"]))
    ]
    g, where = worst_leaf_gap(program["grad_norms"], ref["grad_norms"])
    rows.append(Compared(f"{prefix}grad_norm_gap_worst_leaf({where})", g, limits["grad_norm_gap"]))
    d, where = worst_leaf_gap(program["delta_norms"], ref["delta_norms"])
    rows.append(Compared(f"{prefix}delta_norm_gap_worst_leaf({where})", d, limits["delta_norm_gap"]))
    return rows


def check_training(family, cfg, seed, batches, program, *, lr, block_rows, devices, limits,
                   control: bool) -> Verdict:
    ref = reference_training(family, cfg, seed, batches, lr=lr, block_rows=block_rows,
                             devices=devices)
    log(f"reference followed {len(batches)} steps")
    rows = compare_training(program, ref, limits)
    verdict = Verdict(report(rows), rows)
    if control:
        low = reference_training(
            family, cfg, seed, batches, lr=lr, block_rows=block_rows, devices=devices,
            quant=CONTROL_DTYPE,
        )
        verdict.control_rows = compare_training(low, ref, limits)
        verdict.control_ok = report(verdict.control_rows, label="control")
    return verdict


# ------------------------------------------------------------------- serving


def check_serving(family, cfg: dict, seed: int, served: list[tuple[np.ndarray, np.ndarray]], *,
                  dtype: str, pad_to: int, device, limits: dict, control: bool) -> Verdict:
    """``served``: (prompt ids, served token ids) of the sampled requests.
    One reference pass over each prompt with its served tokens."""
    forward = family.reference.forward
    init = family.make_init(cfg, dtype, layout="reference")
    p = jax.device_put(init(seed_key(seed)), device)

    def gaps(p, seq, quant_too):
        logits = forward(p, seq[None], cfg)[0]
        best = logits.max(-1)
        nxt = jnp.roll(seq, -1)
        served_gap = best - jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        if not quant_too:
            return served_gap, served_gap
        low = forward(p, seq[None], cfg, quant=CONTROL_DTYPE)[0]
        low_gap = best - jnp.take_along_axis(logits, low.argmax(-1)[:, None], axis=-1)[:, 0]
        return served_gap, low_gap

    fn = jax.jit(gaps, static_argnums=(2,))
    worst, worst_low, n_tokens, flips = 0.0, 0.0, 0, 0
    for prompt, toks in served:
        seq = np.zeros((pad_to,), np.int32)
        n = prompt.size + toks.size
        seq[: prompt.size], seq[prompt.size:n] = prompt, toks
        g, low = fn(p, jnp.asarray(seq), control)
        # position t predicts token t + 1: the served tokens sit at
        # prompt.size .. n - 1, predicted from prompt.size - 1 .. n - 2
        span = slice(prompt.size - 1, n - 1)
        worst = max(worst, float(np.asarray(g)[span].max()))
        worst_low = max(worst_low, float(np.asarray(low)[span].max()))
        n_tokens += toks.size
        flips += int((np.asarray(g)[span] > 0).sum())
    log(f"reference pass over {len(served)} requests done")
    print(f"chipbench correct: {len(served)} requests, {n_tokens} served tokens "
          f"against the reference, {flips} not the reference's first", file=sys.stderr)
    rows = [Compared("served_logit_gap_max", worst if served else float("nan"),
                     limits["served_logit_gap"])]
    verdict = Verdict(report(rows), rows)
    if control:
        verdict.control_rows = [
            Compared("served_logit_gap_max", worst_low, limits["served_logit_gap"])
        ]
        verdict.control_ok = report(verdict.control_rows, label="control")
    return verdict
