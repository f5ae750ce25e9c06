"""Decoder-only transformer with pluggable long-context attention.

The reference has no model layer (it only moves gradients); this model
exists to exercise the TPU-first sequence-parallel path
(`horovod_tpu.parallel.ring`) end-to-end: with ``attention="ring"`` or
``"ulysses"`` the module must run inside ``shard_map`` with the sequence
dimension sharded over ``sp_axis`` — each device holds [B, L/n, ...] and
attention is exact over the full sequence.

TPU-first: bf16 compute / f32 params, static shapes, pre-norm blocks,
rotary position embeddings computed from *global* positions so sequence
shards agree.
"""

import contextlib
import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import profile
from horovod_tpu.parallel.ring import ring_attention, ulysses_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_dim: int = 3072
    max_seq_len: int = 8192
    attention: str = "dense"      # dense | flash | ring | ulysses
    # GQA/MQA: number of kv heads (None = num_heads, plain MHA). Must
    # divide num_heads; query head h reads kv head h // (H//G) — the
    # llama convention. Shrinks the k/v projections and lets the flash
    # kernels run the grouped-rows layout (one kv fetch per head
    # group, in-kernel dK/dV group reduction).
    num_kv_heads: Optional[int] = None
    # Fuse rotary embedding into the flash/ring/ulysses kernels' q/k
    # load path (positions derived in-kernel from global offsets —
    # the explicit `positions` input is then unused by attention, so
    # it only works for the standard layouts those offsets describe).
    # The dense path always rotates outside.
    rope_fused: bool = False
    rope_base: float = 10000.0
    sp_axis: Optional[str] = None  # mesh axis holding the sequence shards
    # Ring schedule: "zigzag" is the causal load-balanced layout
    # (parallel.ring.zigzag_shard the tokens/positions/labels; the
    # explicit global `positions` input makes rotary correct for any
    # layout). Only meaningful with attention="ring".
    sp_schedule: str = "contiguous"
    # Megatron-style tensor parallelism: when set, the module runs
    # inside shard_map with attention heads and the MLP hidden dim
    # sharded over this axis (num_heads/mlp_dim are the LOCAL sizes —
    # build with `cfg.local(tp_size)`, place full params with
    # parallel.tensor_parallel.tp_param_specs), and the attention-out
    # / mlp-out projections psum their partial products across it.
    tp_axis: Optional[str] = None
    # Per-head width; defaults to embed_dim // num_heads. Set
    # explicitly when num_heads is a LOCAL (tp-sharded) count.
    head_dim: Optional[int] = None
    # Mixture-of-experts: when moe_experts is set, every
    # `moe_every`-th block swaps its dense MLP for a MoeMlp
    # (parallel/expert.py); ep_axis/ep_size shard the expert dim inside
    # shard_map (tokens should then shard over (dp, ep)). Initialize
    # with ep_axis=None/ep_size=1 (full shapes), apply with the
    # ep-sized config — the tp `local()` pattern.
    moe_experts: Optional[int] = None
    moe_every: int = 2
    # Slots an expert has, as a multiple of tokens / experts; an
    # assignment past them is dropped. None: dropless (sort, grouped
    # matmul over ragged groups, unsort), on one device only.
    moe_capacity_factor: Optional[float] = 1.25
    # Experts a token is sent to, any k up to moe_experts (1: Switch;
    # 2: GShard; OLMoE: 8 of 64).
    moe_top_k: int = 1
    # Divide a token's k routing weights by their sum (GShard); False
    # keeps the softmax probabilities as they are (OLMoE). k = 1 always
    # keeps the raw probability.
    moe_renormalize: bool = True
    # Gated experts, `w_down(silu(w_gate x) * w_up x)`, each of width
    # mlp_dim; False: `w_out(silu(w_in x))`.
    moe_gated: bool = False
    ep_axis: Optional[str] = None
    ep_size: int = 1
    # RMSNorm over the whole query and the whole key projection, before
    # the split into heads and before rotary (OLMoE's QK-norm).
    qk_norm: bool = False
    norm_eps: float = 1e-6        # every RMSNorm's epsilon
    # Passes over the ONE stack of blocks, on the same weights (a looped
    # or universal transformer; Ouro's `total_ut_steps`): `norm_f` closes
    # every pass and its output is the next pass's input. 1: the plain
    # decoder.
    num_passes: int = 1
    # A second RMSNorm on each branch's OUTPUT, before the residual add
    # (`x + rms(attn(rms(x)))`; Ouro's sandwich norm).
    sandwich_norm: bool = False
    # Gated dense feed-forward, `mlp_out(silu(mlp_gate x) * mlp_up x)`,
    # both of width mlp_dim; False: `mlp_out(silu(mlp_in x))`.
    mlp_gated: bool = False
    # An exit gate, Dense(1) with bias, on every pass's normed output;
    # `return_hidden=True` then hands back every pass's hidden states
    # and gate logits for `ops.losses.expected_exit_loss`.
    exit_gate: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.moe_experts is not None and self.ep_axis is not None
                and self.moe_capacity_factor is None):
            raise ValueError("dropless routing (moe_capacity_factor=None) "
                             "is local; with ep_axis set give a capacity "
                             "factor")
        if self.qk_norm and self.tp_axis is not None:
            raise ValueError("qk_norm normalises over all heads' "
                             "projection, which tp_axis shards")
        if self.moe_experts is not None and self.tp_axis is not None:
            # The MoE branch neither psums like the dense row-parallel
            # mlp_out nor shards experts by tp — combining them would
            # silently diverge activations across tp shards.
            raise ValueError("moe_experts cannot be combined with "
                             "tp_axis (MoE blocks are ep-parallel, "
                             "not tensor-parallel)")
        if self.num_passes < 1:
            raise ValueError("num_passes=%d: the stack runs at least once"
                             % self.num_passes)
        if self.num_passes > 1:
            # Not built, so refused by name: the placement rules
            # (tp_param_specs, ep_param_specs) and the sequence-parallel
            # tests know one use of a weight a step, and a routed block
            # would sow its auxiliary losses once a pass.
            for field in ("tp_axis", "sp_axis", "ep_axis", "moe_experts"):
                if getattr(self, field) is not None:
                    raise ValueError("num_passes=%d cannot be combined "
                                     "with %s (the looped stack is built "
                                     "for dense blocks on one device a "
                                     "replica)" % (self.num_passes, field))
        if self.exit_gate and self.num_passes < 2:
            raise ValueError("exit_gate reads the passes of a looped "
                             "stack: give num_passes > 1")
        if self.mlp_gated and self.tp_axis is not None:
            raise ValueError("mlp_gated cannot be combined with tp_axis "
                             "(tp_param_specs places mlp_in and mlp_out "
                             "only)")

    def local(self, tp_size):
        """The per-shard config for `tp_size`-way tensor parallelism."""
        if self.num_heads % tp_size or self.mlp_dim % tp_size:
            raise ValueError(
                "tp_size=%d must divide both num_heads=%d and "
                "mlp_dim=%d" % (tp_size, self.num_heads, self.mlp_dim))
        kv = self.num_kv_heads
        if kv is not None:
            if kv % tp_size:
                raise ValueError(
                    "tp_size=%d must divide num_kv_heads=%d (tensor "
                    "parallelism shards the kv heads too)"
                    % (tp_size, kv))
            kv = kv // tp_size
        return dataclasses.replace(
            self, num_heads=self.num_heads // tp_size,
            num_kv_heads=kv,
            mlp_dim=self.mlp_dim // tp_size,
            head_dim=self.head_dim or self.embed_dim // self.num_heads)


def _rms_norm(cfg, name):
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


def _rotary(x, positions, base=10000.0):
    """Rotary embedding over the last dim; positions [B, L] global."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq  # [B, L, half]
    ang = ang[:, :, None, :]                               # [B, L, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        head_dim = cfg.head_dim or cfg.embed_dim // cfg.num_heads
        G = cfg.num_kv_heads or cfg.num_heads
        if cfg.num_heads % G:
            raise ValueError(
                "num_kv_heads=%d must divide num_heads=%d"
                % (G, cfg.num_heads))
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, head_dim), dtype=cfg.dtype,
            param_dtype=jnp.float32, use_bias=False, name=name)
        q = heads(cfg.num_heads, "query")(x)
        k = heads(G, "key")(x)
        v = heads(G, "value")(x)
        if cfg.qk_norm:
            def whole(t, name):
                flat = t.reshape(t.shape[:-2] + (-1,))
                return _rms_norm(cfg, name)(flat).reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        fused = (cfg.rope_fused and
                 cfg.attention in ("flash", "ring", "ulysses"))
        if not fused:
            q = _rotary(q, positions, cfg.rope_base)
            k = _rotary(k, positions, cfg.rope_base)
        rb = cfg.rope_base if fused else None
        if cfg.attention == "ring":
            o = ring_attention(q, k, v, cfg.sp_axis, causal=True,
                               schedule=cfg.sp_schedule, rotary_base=rb)
        elif cfg.attention == "ulysses":
            o = ulysses_attention(q, k, v, cfg.sp_axis, causal=True,
                                  rotary_base=rb)
        elif cfg.attention == "flash":
            from horovod_tpu.ops import flash_attention
            o = flash_attention(q, k, v, causal=True, rotary_base=rb)
        else:
            if G != cfg.num_heads:
                k = jnp.repeat(k, cfg.num_heads // G, axis=2)
                v = jnp.repeat(v, cfg.num_heads // G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s * (head_dim ** -0.5)
            L = s.shape[-1]
            mask = lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
                lax.broadcasted_iota(jnp.int32, (L, L), 1)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        out = nn.DenseGeneral(cfg.embed_dim, axis=(-2, -1), dtype=cfg.dtype,
                              param_dtype=jnp.float32, use_bias=False,
                              name="out")(o)
        if cfg.tp_axis is not None:
            # Each tp shard projected only its local heads: the row-
            # parallel output is a partial sum (Megatron-style).
            out = lax.psum(out, cfg.tp_axis)
        return out


class Block(nn.Module):
    cfg: TransformerConfig
    moe: bool = False

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        # Under rope_fused=True with a kernel attention (flash/ring/
        # ulysses), `positions` is IGNORED: the kernels apply rotary
        # in-kernel from global row offsets, which assumes the standard
        # contiguous 0..L-1 layout. Custom position ids (packing, shifted
        # windows) require rope_fused=False.
        # `out`: the sandwich norm on a branch's output, or nothing.
        out = (lambda name, h: _rms_norm(cfg, name)(h)) \
            if cfg.sandwich_norm else (lambda name, h: h)
        x = x + out("norm1_out", Attention(cfg, name="attn")(
            _rms_norm(cfg, "norm1")(x), positions))
        h = _rms_norm(cfg, "norm2")(x)
        # `mlp` beside flax's `attn`: the profiler's scope for this half
        # of the block (hvd.profile), dense or routed; no module and no
        # parameter name.
        if self.moe:
            from horovod_tpu.parallel.expert import MoeMlp
            with jax.named_scope("mlp"):
                h = MoeMlp(num_experts=cfg.moe_experts, mlp_dim=cfg.mlp_dim,
                           capacity_factor=cfg.moe_capacity_factor,
                           ep_axis=cfg.ep_axis, ep_size=cfg.ep_size,
                           top_k=cfg.moe_top_k, gated=cfg.moe_gated,
                           renormalize=cfg.moe_renormalize,
                           dtype=cfg.dtype, name="moe_mlp")(h)
            return x + out("norm2_out", h)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=cfg.dtype, param_dtype=jnp.float32, use_bias=False,
            name=name)
        with jax.named_scope("mlp"):
            if cfg.mlp_gated:
                h = nn.silu(dense(cfg.mlp_dim, "mlp_gate")(h)) \
                    * dense(cfg.mlp_dim, "mlp_up")(h)
            else:
                h = nn.silu(dense(cfg.mlp_dim, "mlp_in")(h))
            h = dense(cfg.embed_dim, "mlp_out")(h)
            if cfg.tp_axis is not None:
                # Column-parallel mlp_in -> row-parallel mlp_out: the out
                # product over the local hidden slice is a partial sum.
                h = lax.psum(h, cfg.tp_axis)
        return x + out("norm2_out", h)


class Transformer(nn.Module):
    """tokens [B, L_local] (+ global positions when sequence-sharded) ->
    logits [B, L_local, vocab].

    ``return_hidden=True`` skips the lm_head projection and returns the
    final normed hidden states — pair with
    `horovod_tpu.ops.losses.chunked_softmax_cross_entropy` (and the
    lm_head kernel from the params tree) to train without ever
    materializing the [B, L, vocab] logits: that loss projects a chunk
    of rows at a time and forms both gradients in the same pass, so
    its peak is O(rows x vocab) plus the [D, vocab] f32 and [B, L, D]
    gradients it hands to the backward.

    With ``cfg.num_passes`` > 1 the blocks run that many times on the same
    weights and the logits are the last pass's. With ``cfg.exit_gate``,
    ``return_hidden=True`` returns ``(hidden [T, B, L, D], gate logits
    [T, B, L] f32)`` of all T passes: what
    `horovod_tpu.ops.losses.expected_exit_loss` takes."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
                tokens.shape)
        # The scopes are the profiler's names for the model's parts
        # (hvd.profile); flax's module names (`block_3/attn`) sit inside.
        with jax.named_scope(profile.EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                         param_dtype=jnp.float32, dtype=cfg.dtype,
                         name="embed")(tokens)
        blocks = []
        for i in range(cfg.num_layers):
            moe = (cfg.moe_experts is not None and
                   i % cfg.moe_every == cfg.moe_every - 1)
            blocks.append(Block(cfg, moe=moe, name="block_%d" % i))
        norm_f = _rms_norm(cfg, "norm_f")

        def loop_scope(name):
            # The loop's names exist only where there is a loop.
            return jax.named_scope(name) if cfg.num_passes > 1 \
                else contextlib.nullcontext()

        # The same blocks and the one final norm, `num_passes` times: a
        # pass's normed output is its exit's hidden state and the next
        # pass's input. Unrolled, so that every pass keeps its own name in
        # the profiler's trace.
        exits = []
        with loop_scope(profile.LOOP):
            for t in range(cfg.num_passes):
                with loop_scope(profile.LOOP_PASS % (t + 1)):
                    for block in blocks:
                        with jax.named_scope(profile.BLOCK):
                            x = block(x, positions)
                    with jax.named_scope(profile.HEAD):
                        x = norm_f(x)
                exits.append(x)
        if cfg.exit_gate:
            # Formed in either mode, so that `init` makes the gate beside
            # the head; unused, it is no part of the program.
            with jax.named_scope(profile.EXIT):
                hidden = jnp.stack(exits)
                # In f32: a matrix of one column, and its logits decide
                # every exit's weight in the loss.
                gates = nn.Dense(1, dtype=jnp.float32,
                                 param_dtype=jnp.float32,
                                 name="exit_gate")(hidden)[..., 0]
            if return_hidden:
                return hidden, gates
        if return_hidden:
            return x
        with jax.named_scope(profile.HEAD):
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              param_dtype=jnp.float32, use_bias=False,
                              name="lm_head")(x)
            return logits.astype(jnp.float32)
