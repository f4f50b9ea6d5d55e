"""Expert parallelism — a mixture-of-experts MLP sharded one expert per
rank, with all_to_all token dispatch.

Listed as a non-goal in SURVEY.md §2d (the reference has no MoE);
implemented so the expert-parallel row of the parallelism table is a
working configuration.  Scheme (top-1 routing, capacity-bounded —
Switch-Transformer style):

1. every rank routes its LOCAL tokens: ``argmax(x @ gate_w)`` picks an
   expert, softmax gives the combine weight;
2. tokens are packed into a ``(n_experts, capacity, d)`` dispatch buffer
   (position = running count within the expert; overflow beyond capacity
   is dropped — standard MoE behavior, surfaced in the aux stats);
3. ONE ``all_to_all`` ships row e of every rank to rank e (the expert's
   owner), which runs its expert MLP on all arriving tokens;
4. a second ``all_to_all`` ships results back, and tokens are combined
   into their original positions scaled by the gate weight (dropped
   tokens contribute zero — use MoE layers residually).

Everything is static-shaped (capacity bound), so the whole layer compiles
into the surrounding SPMD program; both all_to_alls ride ICI.

`routed_experts` is the layer WITHOUT a capacity, for a rank that holds a
range of the experts (serving's cut: the router scores all of them, this
rank computes its own experts' part of the sum): top-k over the router's
whole width, the picks that land here sorted by expert, one grouped
product over the held experts' stacked weights, un-sort and gate-weighted
sum.  No token is ever dropped; the work follows the picks.  It is the
one home of routing for the models that are served.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.comm.collectives import all_to_all

EXPERT_AXIS = "expert"
LEAD_ROWS = 128   # `routed_experts` hands the grouped product whole tiles of this many picks


def capacity_for(tokens_per_rank: int, n_experts: int, factor: float = 1.25) -> int:
    """Per-expert per-source-rank slot count."""
    return max(1, math.ceil(tokens_per_rank / n_experts * factor))


def _dispatch_process_combine(
    xv, assign, gate, w_up, w_down, axis_name, cap, activation
):
    """Shared MoE transport: pack ``(R, d)`` virtual tokens into the
    ``(n_experts, cap, d)`` dispatch buffer (cumulative-count slots,
    overflow dropped), ship with ONE all_to_all each way, run the local
    expert MLP, and return each virtual token's gated output (zeros when
    dropped) plus kept mask and per-expert load."""
    n = lax.axis_size(axis_name)
    d = xv.shape[-1]
    onehot = jax.nn.one_hot(assign, n, dtype=jnp.int32)  # (R, n)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1
    pos_in_expert = pos.max(axis=1)  # (R,)
    kept = pos_in_expert < cap
    load = onehot.sum(axis=0)

    dispatch = jnp.zeros((n, cap, d), xv.dtype)
    dispatch = dispatch.at[
        assign, jnp.clip(pos_in_expert, 0, cap - 1)
    ].add(jnp.where(kept[:, None], xv, 0.0))

    arriving = all_to_all(dispatch, axis_name, split_axis=0, concat_axis=0)
    flat = arriving.reshape(n * cap, d)
    hidden = activation(flat @ w_up)
    processed = (hidden @ w_down).reshape(n, cap, d)
    returned = all_to_all(processed, axis_name, split_axis=0, concat_axis=0)

    out_v = returned[assign, jnp.clip(pos_in_expert, 0, cap - 1)]
    yv = jnp.where(kept[:, None], out_v * gate[:, None], 0.0)
    return yv, kept, load


def moe_mlp(
    x: jax.Array,
    gate_w: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-1 MoE MLP inside shard_map over ``axis_name``.

    Args:
      x: local token shard ``(T, d)`` (tokens sharded over the same axis).
      gate_w: replicated router weights ``(d, n_experts)``.
      w_up, w_down: THIS rank's expert parameters ``(d, hidden)`` /
        ``(hidden, d)`` (i.e. the local slice of expert-stacked weights).

    Returns ``(y, stats)`` with ``y: (T, d)`` — the gated expert outputs
    (zeros for dropped tokens) — and routing stats (fraction dropped,
    per-expert load).
    """
    n = lax.axis_size(axis_name)
    T, d = x.shape
    cap = capacity_for(T, n, capacity_factor)

    scores = x @ gate_w  # (T, n)
    probs = jax.nn.softmax(scores, axis=-1)
    assign = jnp.argmax(scores, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, assign[:, None], axis=1)[:, 0]

    y, kept, load = _dispatch_process_combine(
        x, assign, gate, w_up, w_down, axis_name, cap, activation
    )
    stats = {
        "dropped_fraction": jnp.mean(~kept),
        "local_load": load,
    }
    return y, stats


def moe_mlp_top2(
    x: jax.Array,
    gate_w: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 2.0,
    activation=jax.nn.gelu,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-2 MoE MLP (GShard-style) inside shard_map over ``axis_name``.

    Each token is sent to its two highest-probability experts with
    combine weights renormalized over the pair (``g1 + g2 = 1``).  The
    token's two placements are packed as ``2T`` virtual tokens — all
    first choices before all second choices, so first choices win
    capacity — through the same single-all_to_all-each-way transport as
    `moe_mlp`.  Default ``capacity_factor`` doubles to hold the second
    copies.

    ``stats`` additionally carries ``balance_loss``: the Switch/GShard
    load-balancing auxiliary ``n · Σ_e f_e · P_e`` (``f_e`` = fraction of
    tokens whose FIRST choice is e, ``P_e`` = mean router probability) —
    1.0 at perfect balance; add ``pmean(balance_loss) · λ`` to the
    training loss to keep experts utilized.
    """
    n = lax.axis_size(axis_name)
    T, d = x.shape
    cap = capacity_for(T, n, capacity_factor)

    scores = x @ gate_w
    probs = jax.nn.softmax(scores, axis=-1)
    top2_p, top2_e = lax.top_k(probs, 2)  # (T, 2)
    gates = top2_p / jnp.maximum(top2_p.sum(-1, keepdims=True), 1e-9)

    assign = jnp.concatenate([top2_e[:, 0], top2_e[:, 1]])  # (2T,)
    gate = jnp.concatenate([gates[:, 0], gates[:, 1]])
    xv = jnp.concatenate([x, x], axis=0)

    yv, kept, load = _dispatch_process_combine(
        xv, assign, gate, w_up, w_down, axis_name, cap, activation
    )
    y = yv[:T] + yv[T:]

    f = jax.nn.one_hot(top2_e[:, 0], n, dtype=jnp.float32).mean(axis=0)
    balance = n * jnp.sum(f * probs.mean(axis=0))
    stats = {
        "dropped_fraction": jnp.mean(~kept),
        "local_load": load,
        "balance_loss": balance,
    }
    return y, stats


def moe_mlp_expert_choice(
    x: jax.Array,
    gate_w: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 2.0,
    activation=jax.nn.gelu,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Expert-choice MoE MLP (Zhou et al. 2022) inside shard_map: the
    EXPERTS pick their tokens, not the other way around.

    Each expert takes its top-``C`` tokens over the GLOBAL batch by
    router score (``C = T_local · capacity_factor``), so every expert is
    perfectly load-balanced by construction — no balance-loss auxiliary,
    no capacity overflow drops (a token is "dropped" only if no expert
    chose it, which top-scoring tokens never are; it then contributes
    zero, so use the layer residually like the others).

    CAUSALITY CAVEAT: the top-C competition conditions every token's
    routing on the WHOLE batch — including future positions — so this
    layer is for encoder / non-autoregressive models (the paper's
    setting).  A causal LM trained with it would leak future
    information through the routing decisions; that is why
    `TransformerLM(moe_experts=)` uses token-choice top-2, not this.

    Wire pattern (all static shapes): scores all_gather (tiny, T×n),
    identical global top-C on every rank; one ``all_to_all`` ships each
    rank's owned slots of every expert's token list to the expert (rows
    summed on arrival — non-owned slots are zero); the expert MLP runs
    on its (C, d) pick; one ``all_gather`` returns every expert's
    outputs and each rank combines its own tokens weighted by the
    router's softmax-over-experts gate.

    Args/returns mirror `moe_mlp` (stats: total picks owned by this
    rank, mean experts-per-token coverage over this rank's tokens).
    """
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    T, d = x.shape
    # the pick pool is the n·T global tokens — clamp so a generous
    # capacity_factor (or a 1-rank axis) cannot ask top_k for more
    # entries than exist
    cap = max(1, min(int(T * capacity_factor), n * T))

    scores = x @ gate_w  # (T, n) local
    probs = jax.nn.softmax(scores, axis=-1)  # gates: softmax over experts
    # identical global score table on every rank (tiny: T_global × n)
    probs_g = lax.all_gather(probs, axis_name, axis=0, tiled=True)
    Tg = n * T

    # expert e's picks: top-cap GLOBAL token ids by its column, computed
    # identically everywhere (deterministic)
    top_w, top_idx = lax.top_k(probs_g.T, cap)  # (n, cap) each

    # dispatch: this rank owns global tokens [r·T, (r+1)·T); fill the
    # slots whose chosen token lives here, zero elsewhere
    owner = top_idx // T  # (n, cap) source rank of each pick
    local_tok = jnp.clip(top_idx - r * T, 0, T - 1)
    mine = owner == r
    dispatch = jnp.where(mine[:, :, None], x[local_tok], 0.0)  # (n, cap, d)
    arriving = all_to_all(dispatch, axis_name, split_axis=0, concat_axis=0)
    # (n, cap, d): source ranks' partial rows of MY expert — sum fills
    # every slot exactly once (each slot owned by one rank)
    picked = arriving.reshape(n, cap, d).sum(axis=0)  # (cap, d)

    hidden = activation(picked @ w_up)
    out_local = hidden @ w_down  # (cap, d) — my expert's outputs
    # every expert's outputs everywhere (n · cap · d, same order as
    # top_idx rows)
    out_all = lax.all_gather(out_local, axis_name, axis=0)  # (n, cap, d)

    # combine: token t's output = Σ over (e, slot) picks of t:
    #   gate[t, e] · out_all[e, slot]
    flat_idx = top_idx.reshape(-1)  # (n·cap,) global token ids
    flat_out = out_all.reshape(n * cap, d)
    flat_gate = top_w.reshape(-1)  # == probs_g[token, expert] of the pick
    # scatter-add into the GLOBAL token axis, then slice my window —
    # cheaper: mask to my window and scatter into (T, d)
    in_mine = (flat_idx >= r * T) & (flat_idx < (r + 1) * T)
    local_ids = jnp.clip(flat_idx - r * T, 0, T - 1)
    y = jnp.zeros((T, d), x.dtype).at[local_ids].add(
        jnp.where(in_mine[:, None], flat_gate[:, None] * flat_out, 0.0)
    )
    # coverage: how many experts picked each of MY tokens (mean)
    cover = jnp.zeros((T,), jnp.float32).at[local_ids].add(
        jnp.where(in_mine, 1.0, 0.0)
    )
    stats = {
        "local_pick_count": jnp.sum(mine),
        "mean_experts_per_token": cover.mean(),
    }
    return y, stats


def routed_experts(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    top_k: int,
    held: tuple[int, int] | None = None,
    mask: jax.Array | None = None,
    activation=jax.nn.silu,
    scoring: str = "softmax_of_picks",
    bias: jax.Array | None = None,
    scale: float = 1.0,
    zero_experts: int = 0,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-``top_k`` routed gated experts, the part the held experts give.

    Args:
      x: tokens ``(T, d)``.
      router_w: ``(d, n_experts)``, every expert's column.  Scored in
        float32 at ``highest`` precision: a near tie decides a pick.
      w_in, w_out: the HELD experts' stacked weights ``(H, d, 2 * width)``
        (gate's columns first, then the value's) and ``(H, width, d)``.
      held: ``(lo, hi)``, the experts ``lo .. hi - 1`` whose weights these
        are; default all that have weights.
      zero_experts: the LAST so many router outputs are experts with no
        weights (identity: a pick on one adds ``g * x`` and costs no matrix
        product).  They are no chip's to hold: computed here for every
        token, whatever ``held``.
      mask: ``(T,)``, False for a pad token: its picks do no work.
      scoring: how picks and gates come from the router's logits ``r = x @
        router_w``.  ``"softmax_of_picks"``: ``(v, idx) = top_k(r)``, ``g =
        softmax(v)``.  ``"sigmoid_normalised"`` (DeepSeek-V3's gate with no
        groups): ``sig = sigmoid(r)``, ``idx = top_k(sig + bias)`` with
        ``bias (n_experts,)`` a selection bias that does not enter the
        gate, ``g_j = sig_j / sum over the picks of sig``.  ``"softmax"``
        (LongCat-Flash's): ``p = softmax(r)`` over the router's WHOLE width,
        ``idx = top_k(p + bias)``, ``g_j = p_j``, not renormalised over the
        picks.
      scale: what every gate is multiplied by after that (a model's routed
        scaling factor; the shared expert is the caller's and not scaled).

    ``y[t] = sum_j g[t, j] * expert_{idx[t, j]}(x[t])`` over the picks
    with ``idx[t, j]`` held; ``g`` is normalised over ALL ``top_k`` picks,
    held or not: what the absent experts would add is left out, not
    renormalised away.

    Returns ``(y (T, d), counts)``; ``counts``: int32 ``picks`` (real
    tokens x top_k), ``picks_held`` and ``expert_tokens (H,)``, and with
    ``zero_experts`` ``picks_zero``, the real tokens' picks that cost
    nothing.
    """
    T, d = x.shape
    H = w_in.shape[0]
    weighted = router_w.shape[1] - zero_experts   # router outputs that have weights
    lo, hi = held if held is not None else (0, weighted)
    if hi - lo != H:
        raise ValueError(f"held experts [{lo}, {hi}) but weights of {H}")
    with jax.named_scope("moe/router"):
        scores = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if scoring == "softmax_of_picks":
            top_v, top_e = lax.top_k(scores, top_k)
            gates = jax.nn.softmax(top_v, axis=-1)
        elif scoring == "sigmoid_normalised":
            sig = jax.nn.sigmoid(scores)
            _, top_e = lax.top_k(sig if bias is None else sig + bias.astype(jnp.float32), top_k)
            top_v = jnp.take_along_axis(sig, top_e, axis=-1)
            gates = top_v / top_v.sum(axis=-1, keepdims=True)
        elif scoring == "softmax":
            prob = jax.nn.softmax(scores, axis=-1)
            _, top_e = lax.top_k(prob if bias is None else prob + bias.astype(jnp.float32), top_k)
            gates = jnp.take_along_axis(prob, top_e, axis=-1)
        else:
            raise ValueError(
                f"scoring {scoring!r}: 'softmax_of_picks', 'sigmoid_normalised' or 'softmax'")
        if scale != 1.0:
            gates = gates * scale
    with jax.named_scope("moe/sort"):
        real = jnp.ones((T,), bool) if mask is None else mask
        local = top_e.reshape(-1) - lo
        here = (local >= 0) & (local < H) & jnp.repeat(real, top_k)
        group = jnp.where(here, local, H)        # what is not held sorts last
        order = jnp.argsort(group, stable=True)  # picks by expert, then by token
        sizes = jnp.zeros((H + 1,), jnp.int32).at[group].add(1)[:H]
        undo = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=order.dtype))
    with jax.named_scope("moe/experts"):
        width = w_out.shape[1]

        def grouped(tokens):
            """The held experts over the picks' rows in sorted order, as
            many of them as ``tokens`` names: the held picks lead."""
            ab = lax.ragged_dot(x[tokens], w_in, sizes)
            hidden = (activation(ab[:, :width]) * ab[:, width:]).astype(x.dtype)
            return lax.ragged_dot(hidden, w_out, sizes)

        # the grouped product's time follows the rows it is HANDED, not the
        # rows its groups cover (PERF.md section 6, PR 38): where a small
        # share of the experts is held, hand it twice that share of the
        # picks, and all of them only when more than that landed here
        tokens, n = order // top_k, order.size
        lead = LEAD_ROWS * math.ceil(2 * n * H / (router_w.shape[1] * LEAD_ROWS))
        if n < 4 * LEAD_ROWS or 2 * lead > n:
            out = grouped(tokens)
        else:
            out = lax.cond(
                sizes.sum() <= lead,
                lambda: jnp.pad(grouped(tokens[:lead]), ((0, n - lead), (0, 0))),
                lambda: grouped(tokens))
    with jax.named_scope("moe/combine"):
        # rows past the last group were never computed: take none of them
        weight = jnp.where(here, gates.reshape(-1), 0.0)
        picked = jnp.where(here[:, None], out[undo].astype(jnp.float32), 0.0)
        y = (picked * weight[:, None]).reshape(T, top_k, d).sum(axis=1)
    if zero_experts:
        with jax.named_scope("moe/zero"):
            free = (top_e >= weighted) & real[:, None]
            y = y + jnp.where(free, gates, 0.0).sum(axis=-1, keepdims=True) * x.astype(jnp.float32)
    y = y.astype(x.dtype)
    counts = {
        "picks": real.sum(dtype=jnp.int32) * top_k,
        "picks_held": sizes.sum(dtype=jnp.int32),
        "expert_tokens": sizes,
    }
    if zero_experts:
        counts["picks_zero"] = free.sum(dtype=jnp.int32)
    return y, counts


def stack_expert_params(experts: list[dict[str, Any]]) -> dict[str, Any]:
    """Stack per-expert param dicts on a leading axis (shard with
    ``P('expert')`` entering shard_map)."""
    from tpu_dist.utils.tree import stack_pytrees

    return stack_pytrees(experts)
