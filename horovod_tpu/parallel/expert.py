"""Expert parallelism: a routed mixture-of-experts feed-forward, dropless on
one device and with a static capacity where the expert dimension is sharded
over an ``ep`` mesh axis.

The reference framework has no MoE (it is a gradient-reduction library);
this is the TPU-first ``ep`` member of the parallelism family
(dp/sp/tp/pp/ep). One routing function serves every path:

* **route** — float32 softmax over all experts, ``lax.top_k`` for any k,
  the chosen probabilities as weights, renormalised or as they are
  (`route`); the load-balancing loss and the router z-loss beside it
  (`router_losses`). Or by sigmoid (DeepSeek-V3): every expert's score by
  itself, the choice made on score + a selection bias, the weights the
  chosen scores without it, renormalised and scaled.
* **held** (``held=(first, count)``, dropless and local) — the layer is told
  which experts this device holds: the router scores and picks over ALL
  experts, the sorted run of the held experts' rows is taken to the front
  of the k*T-row buffer, `count` groups are multiplied, and what the absent
  experts would add is left out (the one rank's share of an
  expert-parallel layer, without its exchange). Without it every expert
  is held: the run is the whole sorted order.
* **shared** — an always-on gated expert beside the routed ones (`MoeMlp`
  with ``shared_dim``).
* **sort** — the k*T assignments are sorted by expert (`sort_assignments`):
  a permutation, its inverse and the group sizes, which always sum to k*T.
  Nothing here builds a [T, E, C] tensor. A layer told that it holds a PART
  of the experts forms the held experts' run by itself (`held_order`): an
  assignment's position by counting over the bins held, and one sort that
  puts the dead assignments behind them and carries the weights, so that
  neither all k*T are sorted twice nor any [k*T] vector gathered
  (`ops/moe_rows.order_plan` says which; the same rows in the same order).
* **dropless** (``capacity_factor=None``, the local path) — the tokens' rows
  are put into the sorted order (`ops/moe_rows.dispatch`), the experts run
  as a grouped matmul over the contiguous ragged groups (`grouped_matmul`),
  and each token's results are summed with their weights
  (`ops/moe_rows.combine`). No assignment is dropped and an empty group is
  legal; shapes are static ([k*T, D]) whatever the routing. The rows that
  belong to a held expert are live; the dispatch and the combine are told
  their count (k*T where every expert is held) and touch no other. Each
  one's transpose is the other's kernel: no scatter-add of rows in either
  direction. Told which experts it holds, the layer's buffer is the front
  of the sorted order that can be live (count*T rows where that is under
  k*T: a token picks an expert once) from the dispatch to the combine, and
  the activation between the grouped matmuls touches its live tiles alone
  too (`ops/moe_act.activated_matmul`).
* **capacity** (a ``capacity_factor``; required with ``ep_axis``) — each
  expert has ``C = ceil(T/E * capacity_factor)`` slots, filled from the same
  sorted order (first choices of all tokens before second choices, GShard's
  ordering); an assignment past its expert's capacity is dropped (its
  residual path passes through untouched). The [E, C, D] buffers are
  gathered by index, exchanged so each rank runs only its E/ep local
  experts on every rank's tokens (``lax.all_to_all`` split on the expert
  dim, concat on capacity — the MoE analogue of Ulysses' sequence
  all-to-all), and exchanged back.

Router weights are replicated (every rank routes over all E experts);
expert FFN weights are sharded [E/ep, ...] along the expert dim
(PartitionSpec("ep") on axis 0 — see tests/test_expert.py and
__graft_entry__.dryrun_multichip phase 4). Experts are ``w_in -> act ->
w_out`` or gated (``w_gate``, ``w_up``, ``w_down``: SwiGLU with SiLU).
"""

import math
import re
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile
from horovod_tpu.ops import moe_act, moe_rows
from horovod_tpu.ops.grouped_matmul import grouped_matmul, layer_visits

# Final key of every expert-sharded leaf: the contract between `MoeMlp`,
# `ep_param_specs` and `ep_grad_sync`.
EXPERT_LEAVES = ("w_in", "w_out", "w_gate", "w_up", "w_down")


def route(router_logits, k, renormalize=True, scoring="softmax", bias=None,
          scale=1.0):
    """Top-k routing. router_logits: [T, E] (any float dtype; softmax in
    f32). Returns (weights [T, k] f32, experts [T, k] int32, probs [T, E]
    f32): the chosen experts in order of falling probability and their
    probabilities, divided by their sum per token when `renormalize` and
    k > 1 (k = 1 keeps the raw probability: that term is what trains a
    Switch router).

    ``scoring="sigmoid"`` (DeepSeek-V3's `noaux_tc`): probs are each
    expert's own sigmoid; the k experts are those of largest
    ``probs + bias`` (`bias` [E]: a selection bias that moves the choice
    and never the weights; no gradient reaches it), the weights the chosen
    experts' probs, divided by their sum (+ 1e-20) when `renormalize`, then
    multiplied by `scale`."""
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        choice = probs if bias is None else \
            probs + lax.stop_gradient(bias.astype(jnp.float32))
        experts = lax.top_k(choice, k)[1]
        # The chosen scores by comparison, not by a gather (whose transpose
        # is a scatter-add, and which XLA expands under a bare op_name).
        weights = jnp.sum(jnp.where(
            experts[..., None] == jnp.arange(probs.shape[-1]),
            probs[..., None, :], 0.0), axis=-1)
        if renormalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return weights * scale, experts.astype(jnp.int32), probs
    if scoring != "softmax" or bias is not None or scale != 1.0:
        raise ValueError("route: scoring=%r; a selection bias and a scale "
                         "belong to scoring='sigmoid'" % (scoring,))
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, experts = lax.top_k(probs, k)
    if renormalize and k > 1:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, experts.astype(jnp.int32), probs


def router_losses(router_logits, probs, group_sizes):
    """(load-balancing loss, router z-loss) of one layer, both f32 scalars.

    Load balancing as Hugging Face's `load_balancing_loss_func` has it for
    one layer (Switch eq. 4 over all k choices): E * sum_e f_e * P_e with
    f_e = assignments to e / T and P_e the mean probability of e. Even
    routing gives k. The z-loss is mean_t logsumexp(logits_t)^2 (ST-MoE)."""
    T, E = probs.shape
    frac = group_sizes.astype(jnp.float32) / T
    balance = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(
        router_logits.astype(jnp.float32), axis=-1) ** 2)
    return balance, z


def sort_assignments(experts, num_experts):
    """Sorts the k*T assignments of `experts` [T, k] by expert.

    Assignment a = j*T + t is token t's j-th choice (choice-major, so the
    stable sort puts every first choice of an expert before its second
    choices). Returns (flat [kT]: the expert of assignment a; order [kT]:
    the assignment at sorted position s; inv [kT]: the sorted position of
    assignment a; group_sizes [E] int32, which sum to k*T)."""
    flat = experts.T.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    return flat, order, inv, _group_sizes(flat, num_experts)


def _group_sizes(flat, num_experts):
    """How many of the assignments `flat` [kT] each expert takes, [E] int32:
    a count by comparison, not a scatter-add (0.29 ms for 32768 assignments
    on the v5e against microseconds)."""
    return jnp.sum(
        flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype)[None, :],
        axis=0, dtype=jnp.int32)


def held_order(flat, weights, first, sizes):
    """The sorted order of the rows of the experts [first, first + count),
    count = len(sizes), out of the k*T assignments `flat` (`sort_assignments`'
    first result), where the others' rows are never read: by counting over
    the `count` bins held and ONE sort, not by two argsorts over all E.

    `weights` [k, T] f32: each assignment's weight; `sizes` [count] int32:
    the held experts' group sizes, n_live their sum. Returns

    - order [kT] int32: the assignment at sorted position s. Its front of
      n_live positions is the held experts' run of `sort_assignments`' order
      (an expert's first choices before its second), turned to the front;
      behind it the dead assignments, ascending: a permutation.
    - inv [kT] int32: the sorted position of assignment a, counted: its
      bin's start + how many of its bin came before it (the exclusive
      running count down the bin's row of the [count, kT] comparison); kT,
      behind every row, where no held expert takes it.
    - scale [kT] f32: the weights in sorted order, carried by the sort that
      forms `order` (a gather of k*T scalars costs the v5e 0.45 ms, the sort
      of three operands 0.05: `examples/moe_order_sweep.py`). No gradient
      flows through it (`ops/moe_rows.combine`'s `carried`)."""
    count, kT = sizes.shape[0], flat.shape[0]
    hit = flat[None, :] == (first + jnp.arange(count, dtype=flat.dtype)
                            )[:, None]
    live = jnp.any(hit, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    before = jnp.cumsum(hit, axis=1, dtype=jnp.int32) - 1
    inv = jnp.where(live, jnp.sum(
        jnp.where(hit, starts[:, None] + before, 0), axis=0), kT)
    _, order, scale = lax.sort(
        (jnp.where(live, flat - first, count),
         jnp.arange(kT, dtype=jnp.int32),
         lax.stop_gradient(weights).reshape(-1)),
        num_keys=1, is_stable=True)
    return order, inv, scale


def relu2(h):
    """relu(h) squared (Nemotron-H's `relu2`)."""
    return jnp.square(nn.relu(h))


# An expert's activation by the name a configuration gives it.
ACTIVATIONS = {"silu": nn.silu, "relu2": relu2}


def _experts(xs, w_in, w_out, w_gate, act, matmul, last=None):
    """The experts' feed-forward on rows `xs`; `matmul(rows, weights)` is
    grouped (dropless) or batched (capacity). `xs` may be a tuple: the
    rows once for each of a gated expert's two first matmuls, from a
    dispatch that sums their gradients itself (`ops/moe_rows.dispatch`).
    `last(h, gate)`, where given, is the activation and the last matmul
    (`moe_ffn` where it holds a part of the experts)."""
    if not isinstance(xs, tuple):
        xs = (xs,)
    h = matmul(xs[0], w_in)
    gate = None if w_gate is None else matmul(xs[-1], w_gate)
    if last is not None:
        return last(h, gate)
    return matmul(act(h) if gate is None else act(gate) * h, w_out)


def moe_capacity(tokens, num_experts, capacity_factor):
    """Static per-expert capacity (python int)."""
    return max(1, int(math.ceil(tokens / num_experts * capacity_factor)))


def moe_ffn(x, router_w, w_in, w_out, capacity_factor=1.25,
            ep_axis=None, act=nn.silu, top_k=1, w_gate=None,
            renormalize=True, scoring="softmax", bias=None, scale=1.0,
            held=None, rows=None):
    """Routed feed-forward over flattened tokens.

    x: [T, D], whose dtype is the compute dtype (the experts' weights may
    be kept in f32: they are rounded to it where they are used, and their
    gradients come back in their own dtype); router_w: [D, E]
    (replicated); w_in: [E_local, D, F],
    w_out: [E_local, F, D] and, for gated experts, w_gate: [E_local, D, F]
    (`w_out(act(w_gate x) * w_in x)`, so `w_in` is the up projection) —
    E_local = E with ``ep_axis=None``, E/ep inside shard_map with the
    expert dim sharded. ``capacity_factor=None`` is dropless (local only);
    with a factor each assignment consumes one of its expert's capacity
    slots — size it accordingly (>= top_k for comparable drop rates).
    `scoring`, `bias` and `scale` are `route`'s.

    ``rows`` [T, R]: what the experts take where it is not `x` itself
    (experts in a latent: the router reads `x`, the experts a shared
    projection of it); w_in, w_gate are then [E_local, R, F], w_out
    [E_local, F, R] and y is [T, R].

    ``held=(first, count)`` (dropless and local only; without it all E
    are held): this device holds
    the experts [first, first + count) of the E the router scores, so
    w_in, w_out, w_gate are [count, ...]. Every token is routed over all E
    with weights normalised over all its k choices; the result is the sum
    over its choices that are held, and ``stats["held"]`` counts those
    assignments (int32 scalar; the key exists only with `held`).

    Returns (y [T, D] in x.dtype, stats): ``load_balance_loss`` and
    ``router_z_loss`` (f32 scalars, `router_losses`), ``assignments`` ([E]
    int32: what the router sent to each expert, summing to top_k * T),
    ``chosen`` ([T, top_k] int32: each token's experts) and ``dropped``
    (int32 scalar: assignments past their expert's capacity; 0 by
    construction when dropless).
    """
    E = router_w.shape[1]
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    if held is not None:
        first, count = held
        if ep_axis is not None or capacity_factor is not None:
            raise ValueError("held=%r is the dropless local path: it cannot "
                             "be combined with ep_axis or a capacity_factor"
                             % (held,))
        if first < 0 or count < 1 or first + count > E \
                or w_in.shape[0] != count:
            raise ValueError("held=%r of %d experts with %d matrices held"
                             % (held, E, w_in.shape[0]))
    elif w_in.shape[0] * ep != E:
        raise ValueError(
            "expert shards (%d local x ep=%d) != num_experts %d" %
            (w_in.shape[0], ep, E))
    if capacity_factor is None and ep_axis is not None:
        raise ValueError("the dropless path (capacity_factor=None) is "
                         "local; with ep_axis set a capacity_factor is "
                         "required (all_to_all needs static buffers)")
    with jax.named_scope(profile.MOE_ROUTE):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        weights, experts, probs = route(logits, top_k, renormalize,
                                        scoring, bias, scale)
    if rows is not None:
        x = rows
    T, D = x.shape
    # How the sorted order is formed, by what the layer is told it holds.
    counted = capacity_factor is None \
        and moe_rows.order_plan(E, held)[0] == "count"
    with jax.named_scope(profile.MOE_DISPATCH):
        if counted:  # the held experts' run is formed by itself, below
            flat = experts.T.reshape(-1)
            group_sizes = _group_sizes(flat, E)
        else:
            flat, order, inv, group_sizes = sort_assignments(experts, E)
    with jax.named_scope(profile.MOE_ROUTE):
        balance, z = router_losses(logits, probs, group_sizes)
    stats = {"load_balance_loss": balance, "router_z_loss": z,
             "assignments": group_sizes, "chosen": experts}
    weights = weights.T  # [k, T], the assignments' order

    if capacity_factor is None:
        kT = flat.shape[0]
        with jax.named_scope(profile.MOE_DISPATCH):
            # The rows of the experts held here are one run of the sorted
            # order, and the dispatch and the combine are told how many
            # they are (`ops/moe_rows`: kernels that touch the live rows
            # alone where a TPU runs them). With every expert held the
            # run is the whole order and the count k*T, a constant.
            sizes, n_live, carried = group_sizes, jnp.int32(kT), None
            if held is not None:
                # A token picks an expert once, so `count` experts are
                # sent count * T rows at most: where that is under k * T
                # (many choices, few held) the buffer is that long, a
                # static cut, from the dispatch to the combine.
                bound = min(kT, count * T)
                sizes = group_sizes[first:first + count]
                n_live = jnp.sum(sizes)
                stats["held"] = n_live
                if counted:
                    # A part of the assignments is dead: no sort of them
                    # all, no turn to the front, no gather of the weights.
                    every, inv, scale = held_order(flat, weights, first,
                                                   sizes)
                    order = every[:bound]
                    carried = (scale[:bound], every)
                else:
                    # From `start`: turned to the front of the buffer,
                    # which so holds the run whatever the router does. The
                    # rows behind the `n_live` belong to no group.
                    start = jnp.sum(group_sizes[:first])
                    at = jnp.arange(bound, dtype=jnp.int32)
                    order = order[(at + start) % kT]
                    inv = (inv - start) % kT
            xs = moe_rows.dispatch(x, order, inv, n_live, top_k,
                                   1 if w_gate is None else 2)
        with jax.named_scope(profile.MOE_EXPERTS):
            # The three products run over the same rows in the same
            # groups: what their kernels are told of them is formed once.
            meta = layer_visits(sizes, xs[0].shape[0])
            # Told which experts it holds, the layer's activation touches
            # the live tiles of its buffers alone, as the kernels on both
            # sides of it do (`ops/moe_act`, where a TPU runs it and the
            # shapes fit); with every expert held nothing is dead, and
            # XLA's fusion stays.
            last = None if held is None else (
                lambda h, gate: moe_act.activated_matmul(
                    act, h, n_live, w_out, sizes, gate, meta=meta))
            ys = _experts(xs, w_in, w_out, w_gate, act,
                          lambda rows, w: grouped_matmul(rows, w, sizes,
                                                         meta=meta),
                          last)
        with jax.named_scope(profile.MOE_COMBINE):
            y = moe_rows.combine(ys, weights, order, inv, n_live,
                                 carried=carried)
        stats["dropped"] = jnp.zeros((), jnp.int32)
        return y, stats
    C = moe_capacity(T, E, capacity_factor)
    with jax.named_scope(profile.MOE_DISPATCH):
        starts = jnp.cumsum(group_sizes) - group_sizes
        slot = jnp.arange(C, dtype=jnp.int32)[None, :]
        # Slot (e, c) holds the assignment at sorted position
        # starts[e] + c, if expert e has that many.
        taken = slot < group_sizes[:, None]
        token = order[jnp.minimum(starts[:, None] + slot,
                                  order.shape[0] - 1)] % T
        expert_in = jnp.where(taken[..., None], x[token], 0)
        if ep_axis is not None:
            # [E, C, D] -> [E/ep, ep*C, D]: each rank keeps its local
            # experts' slots from EVERY rank's tokens.
            expert_in = lax.all_to_all(expert_in, ep_axis, split_axis=0,
                                       concat_axis=1, tiled=True)
    with jax.named_scope(profile.MOE_EXPERTS):
        out = _experts(expert_in, w_in, w_out, w_gate, act,
                       lambda rows, w: jnp.einsum(
                           "ecd,edf->ecf", rows, w.astype(rows.dtype)))
    with jax.named_scope(profile.MOE_COMBINE):
        if ep_axis is not None:
            # Reverse exchange: [E/ep, ep*C, D] -> [E, C, D].
            out = lax.all_to_all(out, ep_axis, split_axis=1,
                                 concat_axis=0, tiled=True)
        place = inv - starts[flat]  # the assignment's place in its queue
        kept = place < C
        rows = out.reshape(E * C, D)[flat * C + jnp.minimum(place, C - 1)]
        weights = jnp.where(kept.reshape(weights.shape), weights, 0.0)
    stats["dropped"] = jnp.sum(~kept, dtype=jnp.int32)
    with jax.named_scope(profile.MOE_COMBINE):
        y = jnp.einsum("ktd,kt->td", rows.reshape(top_k, T, D), weights,
                       preferred_element_type=jnp.float32)
    return y.astype(x.dtype), stats


class MoeMlp(nn.Module):
    """Drop-in MoE replacement for a transformer MLP: [B, L, D] ->
    [B, L, D], with `moe_ffn`'s statistics sown under ``intermediates``
    (``moe_aux_loss``: the load-balancing loss, ``moe_z_loss``,
    ``moe_assignments``, ``moe_dropped``, ``moe_chosen``;
    `router_aux_losses` and `routing_stats` collect them over the layers).

    ``num_experts`` is GLOBAL; ``ep_size`` is the expert-parallel
    degree the module will be APPLIED under — inside shard_map each
    rank holds [num_experts/ep_size, ...] expert weights, so the
    declared param shapes divide by it (the tp path's `cfg.local()`
    trick). Initialize with ``ep_size=1`` (full shapes), place with
    `ep_param_specs`, apply with the ep-sized module.

    ``scoring="sigmoid"`` routes as DeepSeek-V3 does (`route`): the
    parameter ``select_bias`` [num_experts] f32, zeros, is the selection
    bias, which no gradient reaches, and ``route_scale`` multiplies the
    weights. ``held=(first, count)``: the module holds only those experts'
    matrices ([count, ...]) and routes over all ``num_experts``
    (`moe_ffn`); it sows ``moe_held`` too. ``shared_dim``: an always-on
    gated expert of that width (``shared_gate``, ``shared_up``,
    ``shared_down``), added to the routed sum under scope
    `hvd_moe_shared`."""
    num_experts: int
    mlp_dim: int
    capacity_factor: Optional[float] = 1.25   # None: dropless
    ep_axis: Optional[str] = None
    ep_size: int = 1
    top_k: int = 1
    gated: bool = False
    renormalize: bool = True
    dtype: Any = jnp.bfloat16
    scoring: str = "softmax"
    route_scale: float = 1.0
    held: Optional[Tuple[int, int]] = None
    shared_dim: Optional[int] = None
    act: str = "silu"
    shared_gated: bool = True
    latent_dim: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        if self.num_experts % self.ep_size:
            raise ValueError("ep_size=%d must divide num_experts=%d" %
                             (self.ep_size, self.num_experts))
        e_local = self.num_experts // self.ep_size
        if self.held is not None:  # `moe_ffn` refuses it beside ep_axis
            e_local = self.held[1]
        router_w = self.param("router", nn.initializers.normal(0.02),
                              (D, self.num_experts), jnp.float32)
        bias = None
        if self.scoring == "sigmoid":
            bias = self.param("select_bias", nn.initializers.zeros,
                              (self.num_experts,), jnp.float32)

        def expert(name, rows, cols):
            return self.param(name, nn.initializers.normal(0.02),
                              (e_local, rows, cols), jnp.float32)

        def dense(n, name):
            return nn.Dense(n, dtype=self.dtype, param_dtype=jnp.float32,
                            use_bias=False, name=name)

        R = D if self.latent_dim is None else self.latent_dim
        if self.gated:
            w_gate = expert("w_gate", R, self.mlp_dim)
            w_in = expert("w_up", R, self.mlp_dim)
            w_out = expert("w_down", self.mlp_dim, R)
        else:
            w_gate = None
            w_in = expert("w_in", R, self.mlp_dim)
            w_out = expert("w_out", self.mlp_dim, R)
        act, rows = ACTIVATIONS[self.act], None
        with jax.named_scope(profile.MOE):
            if self.latent_dim is not None:
                with jax.named_scope(profile.MOE_LATENT):
                    rows = dense(R, "latent_in")(
                        x.reshape(-1, D)).astype(self.dtype)
            y, stats = moe_ffn(x.reshape(-1, D).astype(self.dtype), router_w,
                               w_in, w_out,
                               capacity_factor=self.capacity_factor,
                               ep_axis=self.ep_axis, act=act,
                               top_k=self.top_k, w_gate=w_gate,
                               renormalize=self.renormalize,
                               scoring=self.scoring, bias=bias,
                               scale=self.route_scale, held=self.held,
                               rows=rows)
            if self.latent_dim is not None:
                with jax.named_scope(profile.MOE_LATENT):
                    y = dense(D, "latent_out")(y)
            if self.shared_dim is not None:
                with jax.named_scope(profile.MOE_SHARED):
                    xs = x.reshape(-1, D)
                    if self.shared_gated:
                        h = act(dense(self.shared_dim, "shared_gate")(xs)) \
                            * dense(self.shared_dim, "shared_up")(xs)
                    else:
                        h = act(dense(self.shared_dim, "shared_up")(xs))
                    y = y + dense(D, "shared_down")(h)
        for name, key in (("moe_aux_loss", "load_balance_loss"),
                          ("moe_z_loss", "router_z_loss"),
                          ("moe_assignments", "assignments"),
                          ("moe_dropped", "dropped"),
                          ("moe_chosen", "chosen"),
                          ("moe_held", "held")):
            if key in stats:
                self.sow("intermediates", name, stats[key])
        return y.reshape(B, L, D)


def _sown(intermediates, name):
    """Every value sown as `name` anywhere in the tree (one per MoE layer),
    in the order of the stack: the tree's order with the numbers in a
    module's name read as numbers (`block_3` before `block_10`)."""
    def depth(path):
        return [[(0, int(t)) if t.isdigit() else (1, t)
                 for t in re.split(r"(\d+)", str(getattr(k, "key", k)))]
                for k in path]

    found = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if any(getattr(k, "key", None) == name for k in path):
            found.append((depth(path), leaf))
    return [leaf for _, leaf in sorted(found, key=lambda f: f[0])]


def router_aux_losses(intermediates):
    """(load-balancing loss, router z-loss), each the mean over the MoE
    layers whose `MoeMlp` sowed into `intermediates` (`model.apply(...,
    mutable=["intermediates"])`): what a loss function adds to the
    cross-entropy at the weights its job states."""
    balance = _sown(intermediates, "moe_aux_loss")
    z = _sown(intermediates, "moe_z_loss")
    if not balance:
        raise ValueError("no MoeMlp sowed into these intermediates")
    return sum(balance) / len(balance), sum(z) / len(z)


def routing_stats(intermediates):
    """A step's routing statistics from the same tree: ``assignments``
    [layers, E] int32 (each row sums to top_k * tokens), ``chosen``
    [layers, tokens, top_k] int32 (each token's experts) and ``dropped``
    (int32 scalar over all layers; 0 on the dropless path). Where the
    layers hold a part of their experts (`MoeMlp` with ``held``) also
    ``held_share`` [layers] f32: the share of a layer's assignments that
    fell on the experts it holds (count / num_experts under even
    routing)."""
    out = {"assignments": jnp.stack(_sown(intermediates,
                                          "moe_assignments")),
           "chosen": jnp.stack(_sown(intermediates, "moe_chosen")),
           "dropped": sum(_sown(intermediates, "moe_dropped"))}
    held = _sown(intermediates, "moe_held")
    if held:
        out["held_share"] = jnp.stack(held).astype(jnp.float32) \
            / jnp.sum(out["assignments"], axis=1)
    return out


def ep_grad_sync(grads, ep_axis="ep", dp_axis=None, average=False):
    """Synchronizes a raw per-shard gradient tree inside shard_map
    under expert parallelism.

    Contract: differentiate a LOCAL (un-psummed) loss per rank, then
    call this. With tokens sharded over (dp x ep), raw gradients are:

    * expert-sharded leaves (final key in `EXPERT_LEAVES`): already
      summed along ep (the all_to_all transpose routes every ep peer's
      cotangents back to the owning rank) — psum over the dp axes only;
    * replicated leaves (router, norms, ...): this rank's token shard
      only — psum over dp AND ep.

    ``average=False`` (default) yields the gradient of the SUM of
    per-rank local losses; ``average=True`` divides by the total shard
    count (dp x ep), yielding the gradient of their MEAN — use this to
    match `tensor_parallel.tp_grad_sync`'s dp-averaging convention.
    `dp_axis` may be a name or tuple of names.
    """
    dp_axes = ()
    if dp_axis is not None:
        dp_axes = (dp_axis,) if isinstance(dp_axis, str) else tuple(dp_axis)
    total = 1.0
    if average:
        for ax in dp_axes + (ep_axis,):
            total = total * lax.axis_size(ax)

    def sync(path, g):
        axes = list(dp_axes)
        # Same final-key rule as ep_param_specs — the two halves of
        # the placement/sync contract must classify leaves identically.
        if not _is_expert_leaf(path):
            axes.append(ep_axis)
        for ax in axes:
            g = lax.psum(g, ax)
        if average:
            g = g / total
        return g

    return jax.tree_util.tree_map_with_path(sync, grads)


def _is_expert_leaf(path):
    return bool(path) and getattr(path[-1], "key", None) in EXPERT_LEAVES


def ep_param_specs(params, ep_axis, replicated_spec=None):
    """PartitionSpecs for a params tree containing MoeMlp leaves:
    expert-dim sharding for the expert weights, replication elsewhere.

    Walks the tree by key name (the MoeMlp param names are the
    contract), mirroring `tensor_parallel.tp_param_specs`."""
    from jax.sharding import PartitionSpec as P

    rep = replicated_spec if replicated_spec is not None else P()
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: P(ep_axis) if _is_expert_leaf(path) else rep,
        params)
