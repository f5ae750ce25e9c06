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
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu import profile
from horovod_tpu.parallel.ring import ring_attention, ulysses_attention


# What a layer of `TransformerConfig.attention_types` may be: plain attention
# under the causal triangle ("full") or the causal band ("window"), or a mixer
# of its own in attention's place ("kda": `KimiDeltaAttention`; "conv":
# `GatedShortConv`).
ATTENTION_KINDS = ("full", "window", "kda", "conv")


class Yarn(NamedTuple):
    """YaRN's static rescaling of the rotary frequencies (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V2's `rope_scaling` states it): the
    frequencies that turn less than `beta_slow` times over
    `original_len` positions are divided by `factor`, those that turn more
    than `beta_fast` times are kept, the ones between are blended."""
    factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_len: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


class AttentionShape(NamedTuple):
    """What an attention KIND's layers are beyond their mask
    (`TransformerConfig.attention_shapes`, a `Layer`'s `shape`; Laguna's
    `num_attention_heads_per_layer` and `rope_parameters` by `layer_types`):
    each field None where the kind keeps the stack's own (`num_heads`;
    `rope_base`; the whole head rotated; `rope_yarn` on the full layers)."""
    # Query heads, on the stack's `num_kv_heads` (which must divide them).
    num_heads: Optional[int] = None
    rope_base: Optional[float] = None
    # The FIRST `rotary_dim` channels of each head of q and k are rotated
    # (rotate-half pairs inside that slice; `partial_rotary_factor` x
    # head_dim, transformers' convention), the others pass as they are.
    rotary_dim: Optional[int] = None
    # YaRN's rescaling, its frequencies formed over the rotated slice.
    rope_yarn: Optional[Yarn] = None


class Layer(NamedTuple):
    """What ONE block of the stack is (`TransformerConfig.layers` makes
    them, `Block` runs them): its branches in order, each
    `x + out_norm(mixer(norm(x)))` (under `hc_mult` > 1 the same mixer
    inside a hyper-connection). The default is the plain two-branch block:
    causal attention, then the dense feed-forward. The sizes are the
    configuration's; a new kind of layer is a new value here and an entry
    in `_mixer`."""
    # The mixers: "attn" (`Attention`) | "latent" (`LatentAttention`) |
    # "kda" (`KimiDeltaAttention`) | "conv" (`GatedShortConv`) | "ssm"
    # (`Mamba2`) | "mlp" (the dense feed-forward) | "moe" (the routed one).
    branches: Tuple[str, ...] = ("attn", "mlp")
    # The parameter name of each branch's norm, and of the sandwich norm
    # on its output (None: no such norm).
    norms: Tuple[str, ...] = ("norm1", "norm2")
    out_norms: Tuple[Optional[str], ...] = (None, None)
    # The attention branch's kind under `attention_types`: "window" (an
    # "attn" branch under `ops.BandMask`), "full" (an "attn" branch under
    # YaRN's rotation, or a "latent" one); None: the stack's one mask
    # (causal, or `attention_mask`). A kind is a name in the trace too
    # (`profile.ATTN_KINDS`).
    kind: Optional[str] = None
    # Whether the block keeps only its input for the backward pass and
    # runs its forward again there.
    remat: bool = False
    # What the kind's attention is beyond mask and name (heads, rotary base
    # and width, YaRN); None: the configuration's own fields.
    shape: Optional[AttentionShape] = None


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
    # kernels take a kv head's query heads as the rows of one tile (one kv
    # fetch per head group, in-kernel dK/dV group reduction).
    num_kv_heads: Optional[int] = None
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
    # the split into heads and before rotary (True: OLMoE's QK-norm), or
    # "head": over each head's `head_dim` alone, one learned [head_dim]
    # scale for the queries and one for the keys shared by the heads
    # (Qwen3's).
    qk_norm: Union[bool, str] = False
    # The attention mask of every block: None is causal; else a rule over
    # (query position, key position) that `ops.flash_attention` knows
    # (`ops.BlockDiffusionMask`: a noisy and a clean copy of the sequence
    # side by side, `models.block_diffusion_batch`). The rule's positions
    # are the ROWS of the sequence handed in; `positions` (rotary) are the
    # caller's and may repeat. attention="dense" or "flash".
    attention_mask: Optional[Any] = None
    # An attention kind a layer of the two-branch block, `num_layers` of
    # `ATTENTION_KINDS`: "kda" and "conv" are mixers of their own (below),
    # "full" | "window" plain attention (Gemma's and Qwen's `layer_types` of
    # `full_attention` / `sliding_attention`): a "window" layer's query sees
    # itself and the `attention_window` - 1 keys before it
    # (`ops.BandMask`), a "full" layer's every key before it. Both rotate
    # by `rope_base`; `rope_yarn` rescales the FULL layers' frequencies
    # alone (a window never reaches past the trained context). None: every
    # layer is causal, or under `attention_mask`. attention="dense" or
    # "flash".
    attention_types: Optional[Tuple[str, ...]] = None
    attention_window: Optional[int] = None
    # What a kind's layers are beyond their mask, `(kind, AttentionShape)`
    # pairs for "full" and / or "window" (one record by kind, hashable):
    # query heads on the same `num_kv_heads`, rotary base, how many of a
    # head's channels are rotated, YaRN over that slice (Laguna: 48 heads,
    # half the head on YaRN's frequencies at base 500000 on a full layer; 64
    # heads, the whole head at base 10000 on a window layer). A kind without
    # a pair, and a field left None, keep `num_heads`, `rope_base`, the whole
    # head and `rope_yarn` on the full layers.
    attention_shapes: Optional[Tuple[Tuple[str, AttentionShape], ...]] = None
    # "head": every head's output times sigmoid(the branch's normed input
    # x `gate` [embed_dim, heads]) before `out`, the sigmoid in f32 (Laguna's
    # `gating` per head). None: no gate.
    attention_gate: Optional[str] = None
    # A "kda" layer of `attention_types` is a Kimi Delta Attention mixer
    # (`KimiDeltaAttention`; Kimi Linear, arXiv:2510.26692) in attention's
    # place: `num_heads` heads of `kda_head_dim` channels (the two low ranks
    # are as wide), a causal depthwise convolution of `kda_conv` taps on q, k
    # and v, the recurrence in chunks of `kda_chunk` tokens (`ops/kda.py`);
    # the decay's starting values as `ssm_dt_init` says. It reads no
    # position, and beside it a "full" layer may be latent attention
    # (`kv_lora_rank`) with `rotary=False`: the KDA layers' decay is the
    # stack's position.
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    # A "conv" layer of `attention_types` is a double-gated short
    # convolution (`GatedShortConv`; LFM2, arXiv:2511.23404) in attention's
    # place: `embed_dim` channels, a causal depthwise convolution of
    # `conv_taps` taps (LFM2's `conv_L_cache`) between two gates, no
    # activation, no bias. It reads no position.
    conv_taps: int = 3
    # The head is the embedding: no `lm_head`; the logits are the normed
    # state times the table transposed, and a loss takes
    # `params["embed"]["embedding"].T` as its kernel, so the table's gradient
    # is the lookup's plus the head's.
    tie_embeddings: bool = False
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
    # The first `first_k_dense` blocks keep the dense feed-forward whatever
    # `moe_every` says (DeepSeek's `first_k_dense_replace`): block i is
    # routed where i >= first_k_dense and i % moe_every == moe_every - 1.
    first_k_dense: int = 0
    # Width of ONE routed expert where it differs from the dense
    # feed-forward's `mlp_dim` (None: `mlp_dim` serves both).
    moe_dim: Optional[int] = None
    # "softmax" over the experts, or each expert's own "sigmoid" with a
    # selection bias, the weights renormalised and multiplied by
    # `moe_route_scale` (DeepSeek-V3; `parallel.expert.route`).
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    # Width of an always-on gated expert beside the routed ones.
    moe_shared_dim: Optional[int] = None
    # (first, count): the experts of `moe_experts` this device HOLDS. The
    # router scores all of them; what the absent ones would add is left out
    # (one rank's share of an expert-parallel layer, without the exchange).
    moe_held: Optional[Tuple[int, int]] = None
    # Latent attention (DeepSeek-V2's MLA) where `kv_lora_rank` is set: keys
    # and values from one normed projection of that width, queries through
    # a normed one of `q_lora_rank`, or where that is None (DeepSeek-V2-Lite,
    # Kanana-2) straight from the state; a head's q.k is
    # `qk_nope_dim` wide without position and `qk_rope_dim` wide with
    # rotary, the rotary key ONE a token for all heads; values are
    # `v_head_dim` wide. `head_dim`, `num_kv_heads` and `qk_norm` do not
    # apply. `rope_yarn` rescales the rotary slice's frequencies and the
    # softmax scale (beside `attention_types`: the full layers' whole-head
    # rotation of plain attention, cos and sin times its factor).
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_yarn: Optional[Yarn] = None
    # Hyper-connections (Zhu et al., arXiv:2409.19606; manifold-constrained,
    # arXiv:2512.24880): the residual path is `hc_mult` streams; each branch
    # of a block reads ONE input mixed from them and writes its output back
    # into all, the streams themselves mixed by a doubly-stochastic map of
    # `hc_sinkhorn_iters` Sinkhorn iterations (`HyperConnection`). 1: the
    # plain residual. `hc_remat` recomputes the maps and the read in the
    # backward pass instead of keeping their stream-wide intermediates.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    hc_remat: bool = False
    # The first `block_remat` blocks keep only their input for the backward
    # pass and run their forward again there (`jax.checkpoint` around the
    # block): about 1 GiB a block at 4096 tokens, 3584 wide and four
    # streams, for a third more of the block's time.
    block_remat: int = 0
    # Multi-token prediction modules behind the stack (DeepSeek-V3 §2.2),
    # 0 or 1: the module takes the stack's last state and the NEXT token's
    # embedding through one more (routed) block, and `return_hidden=True`
    # hands back its normed state beside the stack's, for the loss on the
    # token after next through the same head.
    mtp_depth: int = 0
    # A layer pattern (Nemotron-H's `hybrid_override_pattern`): one kind a
    # layer, `num_layers` of them, each layer ONE mixer behind one norm,
    # `x + mixer(rms(x))`: "ssm" a Mamba-2 mixer (`Mamba2`, the `ssm_*`
    # sizes), "attn" attention alone, "moe" the routed feed-forward alone
    # (the `moe_*` fields), "mlp" the dense one. None: every layer is the
    # two-branch block, routed where `moe_every` / `first_k_dense` say.
    layer_types: Optional[Tuple[str, ...]] = None
    # False: attention reads no position (no rotary on q and k; a causal
    # stack behind a recurrence needs none).
    rotary: bool = True
    # A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060): `ssm_heads` heads of
    # `ssm_head_dim` channels (their product the mixer's inner width),
    # B and C shared by the heads of each of `ssm_groups` groups, a state
    # of `ssm_state` a channel, a causal depthwise convolution of `ssm_conv`
    # taps, the recurrence in chunks of `ssm_chunk` tokens (`ops/ssd.py`).
    # `ssm_dt_init`: (least, largest, floor) of the log-uniform time steps
    # the step bias starts at.
    ssm_heads: Optional[int] = None
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_init: Tuple[float, float, float] = (0.001, 0.1, 1e-4)
    # The routed experts run in a latent of this width between two shared
    # projections (LatentMoE; `parallel.expert.MoeMlp`); the router and the
    # shared expert read the state itself.
    moe_latent_dim: Optional[int] = None
    # The routed and the shared experts' activation ("silu" | "relu2"),
    # and whether the shared expert is gated like DeepSeek's or
    # `down(act(up x))`.
    moe_act: str = "silu"
    moe_shared_gated: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.moe_experts is not None and self.ep_axis is not None
                and self.moe_capacity_factor is None):
            raise ValueError("dropless routing (moe_capacity_factor=None) "
                             "is local; with ep_axis set give a capacity "
                             "factor")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError("qk_norm=%r: True (the whole projection) or "
                             "'head' (each head's own)" % (self.qk_norm,))
        if self.qk_norm is True and self.tp_axis is not None:
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
        # Built for one device a replica, so refused by name elsewhere: the
        # placement rules and the sequence-parallel kernels know none of
        # these parameters, and a looped stack would sow a routed block's
        # statistics once a pass.
        new = [name for name, on in (
            ("first_k_dense", self.first_k_dense > 0),
            ("moe_dim", self.moe_dim is not None),
            ("moe_scoring", self.moe_scoring != "softmax"),
            ("moe_shared_dim", self.moe_shared_dim is not None),
            ("moe_held", self.moe_held is not None),
            ("kv_lora_rank", self.kv_lora_rank is not None),
            ("hc_mult", self.hc_mult > 1),
            ("block_remat", self.block_remat > 0),
            ("mtp_depth", self.mtp_depth > 0),
            ("qk_norm='head'", self.qk_norm == "head"),
            ("attention_mask", self.attention_mask is not None),
            ("attention_types", self.attention_types is not None),
            ("attention_shapes", self.attention_shapes is not None),
            ("attention_gate", self.attention_gate is not None),
            ("layer_types", self.layer_types is not None),
            ("rotary=False", not self.rotary),
            ("moe_latent_dim", self.moe_latent_dim is not None),
            ("moe_act", self.moe_act != "silu"),
            ("moe_shared_gated=False", not self.moe_shared_gated),
            ("tie_embeddings", self.tie_embeddings)) if on]
        for field in ("tp_axis", "sp_axis", "num_passes"):
            on = (self.num_passes > 1 if field == "num_passes"
                  else getattr(self, field) is not None)
            if on and new:
                raise ValueError("%s cannot be combined with %s (built for "
                                 "one stack of blocks on one device a "
                                 "replica)" % (field, ", ".join(new)))
        if self.moe_held is not None and self.ep_axis is not None:
            raise ValueError("moe_held cannot be combined with ep_axis (a "
                             "device that is told which experts it holds "
                             "runs no exchange)")
        if self.moe_held is not None and self.moe_capacity_factor is not None:
            raise ValueError("moe_held is the dropless path: give "
                             "moe_capacity_factor=None")
        if self.kv_lora_rank is not None and self.attention not in (
                "dense", "flash"):
            raise ValueError("kv_lora_rank (latent attention) runs with "
                             "attention='dense' or 'flash', not %r"
                             % self.attention)
        if self.attention_mask is not None and (
                self.attention not in ("dense", "flash")
                or self.kv_lora_rank is not None):
            raise ValueError("attention_mask runs with attention='dense' or "
                             "'flash' and plain attention, not %r%s"
                             % (self.attention, " with kv_lora_rank"
                                if self.kv_lora_rank is not None else ""))
        if (self.rope_yarn is not None and self.kv_lora_rank is None
                and self.attention_types is None):
            raise ValueError("rope_yarn rescales latent attention's rotary "
                             "slice (give kv_lora_rank) or the full layers' "
                             "rotation (give attention_types)")
        if self.attention_types is not None:
            self._check_attention_types()
        if self.attention_shapes is not None:
            self._check_attention_shapes()
        if self.attention_gate not in (None, "head"):
            raise ValueError("attention_gate=%r: 'head' (one sigmoid a head) "
                             "or None" % (self.attention_gate,))
        if self.attention_gate is not None:
            # `Attention` alone has the gate: latent attention and the KDA
            # mixer (which has a gate of its own) know none.
            for field, on in (
                    ("kv_lora_rank", self.kv_lora_rank is not None),
                    ("'kda' layers in attention_types",
                     "kda" in (self.attention_types or ())),
                    ("layer_types", self.layer_types is not None)):
                if on:
                    raise ValueError("attention_gate cannot be combined "
                                     "with %s (the gate is plain "
                                     "attention's, in the two-branch block)"
                                     % field)
        if self.hc_mult < 1:
            raise ValueError("hc_mult=%d: the residual path has at least "
                             "one stream" % self.hc_mult)
        if self.conv_taps < 1:
            raise ValueError("conv_taps=%d: a convolution has at least the "
                             "tap on the current token" % self.conv_taps)
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth=%d: one multi-token prediction "
                             "module is built, not a chain of them"
                             % self.mtp_depth)
        if (not self.rotary and self.kv_lora_rank is not None
                and "kda" not in (self.attention_types or ())):
            raise ValueError("rotary=False cannot be combined with "
                             "kv_lora_rank (latent attention's rotary slice "
                             "is its only position) but beside 'kda' layers "
                             "in attention_types, whose decay is one")
        if self.moe_act not in ("silu", "relu2"):
            raise ValueError("moe_act=%r: 'silu' or 'relu2'"
                             % (self.moe_act,))
        if self.layer_types is not None:
            self._check_layer_types()

    def _check_attention_types(self):
        """What an attention kind a layer cannot be placed beside, by
        name."""
        kinds = ATTENTION_KINDS
        types = self.attention_types
        if len(types) != self.num_layers or any(t not in kinds
                                                for t in types):
            raise ValueError("attention_types=%r: num_layers=%d kinds, each "
                             "of %s" % (types, self.num_layers,
                                        ", ".join(kinds)))
        if "window" in types and (self.attention_window or 0) < 1:
            raise ValueError("attention_types names a 'window' layer: give "
                             "attention_window (the keys a query sees, "
                             "itself among them)")
        # The band is a rule of the flash kernels and of the dense form;
        # the sequence-parallel paths know `causal` alone, one mask serves
        # a stack under `attention_mask`, latent attention has its own
        # rotation and no band (its "full" layers stand beside "kda" ones
        # alone), a layer pattern's "attn" layers have no kind, nor do
        # the streams' blocks and the prediction module's.
        for field, on in (
                ("attention=%r" % self.attention,
                 self.attention in ("ring", "ulysses")),
                ("attention_mask", self.attention_mask is not None),
                ("kv_lora_rank", self.kv_lora_rank is not None
                 and ("kda" not in types or "window" in types)),
                ("layer_types", self.layer_types is not None),
                ("hc_mult", self.hc_mult > 1),
                ("mtp_depth", self.mtp_depth > 0)):
            if on:
                raise ValueError("attention_types cannot be combined with "
                                 "%s (%s layers are built for the "
                                 "two-branch block, its plain attention "
                                 "under attention='dense' or 'flash')"
                                 % (field, ", ".join(kinds)))

    def _check_attention_shapes(self):
        """What a shape by kind must be, and cannot be placed beside."""
        shapes = self.attention_shapes
        kinds = [pair[0] for pair in shapes]
        types = self.attention_types or ()
        if (len(set(kinds)) != len(kinds)
                or any(k not in ("full", "window") or k not in types
                       for k in kinds)
                or any(not isinstance(s, AttentionShape) for _, s in shapes)):
            raise ValueError("attention_shapes=%r: (kind, AttentionShape) "
                             "pairs, a kind once, each 'full' or 'window' "
                             "and named in attention_types=%r"
                             % (shapes, self.attention_types))
        if self.kv_lora_rank is not None:
            raise ValueError("attention_shapes cannot be combined with "
                             "kv_lora_rank (latent attention has its own "
                             "widths and its one rotary slice)")
        head_dim = self.head_dim or self.embed_dim // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        for kind, shape in shapes:
            if shape.num_heads is not None and (
                    self.head_dim is None or shape.num_heads % kv_heads):
                raise ValueError("attention_shapes gives %r layers %d heads: "
                                 "give head_dim (embed_dim // num_heads "
                                 "would differ by kind) and a multiple of "
                                 "the %d kv heads"
                                 % (kind, shape.num_heads, kv_heads))
            if shape.rotary_dim is not None and not (
                    0 < shape.rotary_dim <= head_dim
                    and shape.rotary_dim % 2 == 0):
                raise ValueError("attention_shapes gives %r layers "
                                 "rotary_dim=%d: an even number of a head's "
                                 "%d channels" % (kind, shape.rotary_dim,
                                                  head_dim))

    def _check_layer_types(self):
        """What a layer pattern cannot be placed beside, by name."""
        kinds = ("ssm", "attn", "moe", "mlp")
        types = self.layer_types
        if len(types) != self.num_layers or any(t not in kinds
                                                for t in types):
            raise ValueError("layer_types=%r: num_layers=%d kinds, each of "
                             "%s" % (types, self.num_layers,
                                     ", ".join(kinds)))
        # Built as a plain stack of one-mixer layers on one device a
        # replica: the streams' and the module's blocks are two-branch
        # blocks, the exchange of an expert-parallel layer and the sandwich
        # norms know no layer of another kind.
        for field, on in (("hc_mult", self.hc_mult > 1),
                          ("mtp_depth", self.mtp_depth > 0),
                          ("ep_axis", self.ep_axis is not None),
                          ("sandwich_norm", self.sandwich_norm),
                          ("first_k_dense", self.first_k_dense > 0)):
            if on:
                raise ValueError("layer_types cannot be combined with %s "
                                 "(the pattern says what each layer is; it "
                                 "is built for a plain stack of one-mixer "
                                 "layers)" % field)
        if "moe" in types and self.moe_experts is None:
            raise ValueError("layer_types names a 'moe' layer: give "
                             "moe_experts (and the moe_* sizes)")
        if "ssm" in types:
            if self.ssm_heads is None:
                raise ValueError("layer_types names an 'ssm' layer: give "
                                 "ssm_heads (and the ssm_* sizes)")
            if self.ssm_heads % self.ssm_groups:
                raise ValueError("ssm_groups=%d must divide ssm_heads=%d "
                                 "(a group's B and C serve whole heads)"
                                 % (self.ssm_groups, self.ssm_heads))

    def layers(self):
        """What each block of the model is, a `Layer` a block: `num_layers`
        of them, `block_<i>`'s at i, and behind them the prediction
        module's `mtp_block` where `mtp_depth` is set. The one reader of
        the fields that spell a layer (`layer_types`; `attention_types`,
        whose "kda" and "conv" layers are mixers of their own in
        attention's place, and `attention_shapes`; `moe_every` and
        `first_k_dense`; `kv_lora_rank` as a choice of attention;
        `sandwich_norm` as names; `block_remat`)."""
        attention = "latent" if self.kv_lora_rank is not None else "attn"
        shapes = dict(self.attention_shapes or ())

        def two_branch(mixer, routed, kind=None, remat=False):
            norms = ("norm1", "norm2")
            return Layer((mixer, "moe" if routed else "mlp"), norms,
                         tuple(name + "_out" if self.sandwich_norm else None
                               for name in norms), kind, remat,
                         shapes.get(kind))

        table = []
        for i in range(self.num_layers):
            remat = i < self.block_remat
            if self.layer_types is not None:  # one mixer behind one norm
                table.append(Layer((self.layer_types[i],), ("norm",),
                                   (None,), None, remat))
                continue
            kind = None if self.attention_types is None \
                else self.attention_types[i]
            routed = (self.moe_experts is not None
                      and i >= self.first_k_dense
                      and i % self.moe_every == self.moe_every - 1)
            mixer, kind = (kind, None) if kind in ("kda", "conv") \
                else (attention, kind)
            table.append(two_branch(mixer, routed, kind, remat))
        if self.mtp_depth:  # routed wherever the model is, never recomputed
            table.append(two_branch(attention, self.moe_experts is not None))
        return tuple(table)

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


def yarn_inv_freq(dim, base, yarn):
    """The `dim // 2` rotary frequencies of a `dim`-wide slice under
    `yarn` (python floats: static). f_i = base^(-2i/dim) is divided by
    `factor` where i is past `hi`, kept where it is before `lo`, blended
    linearly between: (lo, hi) the indices whose frequencies turn
    `beta_fast` and `beta_slow` times over `original_len` positions."""
    def turns_at(turns):
        return (dim * math.log(yarn.original_len / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    lo = max(math.floor(turns_at(yarn.beta_fast)), 0)
    hi = min(math.ceil(turns_at(yarn.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    out = []
    for i in range(dim // 2):
        f = base ** (-2.0 * i / dim)
        keep = 1.0 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(f / yarn.factor * (1.0 - keep) + f * keep)
    return out


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotary_freq(x, positions, inv_freq, mscale=1.0):
    """`_rotary` with given frequencies `inv_freq` [D/2]; cos and sin are
    multiplied by `mscale`."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1):

        c_q = rms(W_qa h);  [q_nope | q_rope] = W_qb c_q   per head
        (`q_lora_rank` None: [q_nope | q_rope] = W_q h, no W_qa, no norm)
        [c_kv | k_rope] = W_kva h;  c_kv = rms(c_kv)
        [k_nope | v] = W_kvb c_kv                           per head
        s = scale * (q_nope.k_nope + rot(q_rope).rot(k_rope)),  causal

    with k_rope ONE key a token for all heads. ``attention="flash"`` hands
    the two products to the flash kernels as they are (`q_shared`,
    `k_shared`: the shared key is never repeated over the heads);
    ``"dense"`` is the plain einsum form. scale = (nope + rope)^-1/2, times
    YaRN's mscale squared under `rope_yarn`."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        H, nope, rope, vd = (cfg.num_heads, cfg.qk_nope_dim,
                             cfg.qk_rope_dim, cfg.v_head_dim)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=cfg.dtype, param_dtype=jnp.float32, use_bias=False,
            name=name)
        heads = lambda d, name: nn.DenseGeneral(  # noqa: E731
            (H, d), dtype=cfg.dtype, param_dtype=jnp.float32,
            use_bias=False, name=name)
        # The work outside the kernels under the profiler's three names
        # (`profile.ATTN_PARTS`): no module and no parameter name.
        if cfg.q_lora_rank is not None:
            with jax.named_scope(profile.ATTN_PROJ):
                c_q = dense(cfg.q_lora_rank, "q_a")(x)
            with jax.named_scope(profile.ATTN_NORM):
                c_q = _rms_norm(cfg, "q_norm")(c_q)
        with jax.named_scope(profile.ATTN_PROJ):
            q = heads(nope + rope, "q_b")(c_q) \
                if cfg.q_lora_rank is not None else heads(nope + rope, "q")(x)
            kv = dense(cfg.kv_lora_rank + rope, "kv_a")(x)
        with jax.named_scope(profile.ATTN_ROPE):
            k_rope = kv[..., None, cfg.kv_lora_rank:]    # [B, L, 1, rope]
            c_kv = kv[..., :cfg.kv_lora_rank]
        with jax.named_scope(profile.ATTN_NORM):
            c_kv = _rms_norm(cfg, "kv_norm")(c_kv)
        with jax.named_scope(profile.ATTN_PROJ):
            kv = heads(nope + vd, "kv_b")(c_kv)
        with jax.named_scope(profile.ATTN_ROPE):
            q_nope, q_rope = q[..., :nope], q[..., nope:]
            k_nope, v = kv[..., :nope], kv[..., nope:]
        scale = (nope + rope) ** -0.5
        yarn = cfg.rope_yarn
        if yarn is None:
            inv_freq = [cfg.rope_base ** (-2.0 * i / rope)
                        for i in range(rope // 2)]
            m = 1.0
        else:
            inv_freq = yarn_inv_freq(rope, cfg.rope_base, yarn)
            m = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
            scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
        if cfg.rotary:  # else no position: beside "kda" layers alone
            with jax.named_scope(profile.ATTN_ROPE):
                q_rope = _rotary_freq(q_rope, positions, inv_freq, m)
                k_rope = _rotary_freq(k_rope, positions, inv_freq, m)
        if cfg.attention == "flash":
            from horovod_tpu.ops import flash_attention
            o = flash_attention(q_nope, k_nope, v, causal=True, scale=scale,
                                q_shared=q_rope, k_shared=k_rope)
        else:
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0],
                              preferred_element_type=jnp.float32)) * scale
            L = s.shape[-1]
            mask = lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
                lax.broadcasted_iota(jnp.int32, (L, L), 1)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        with jax.named_scope(profile.ATTN_PROJ):
            return nn.DenseGeneral(
                cfg.embed_dim, axis=(-2, -1), dtype=cfg.dtype,
                param_dtype=jnp.float32, use_bias=False, name="out")(o)


def sinkhorn(m, iters, eps):
    """`iters` times rows then columns of a positive [..., n, n] map: each
    row divided by its sum + eps, then each column by its: doubly stochastic
    in the limit."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_maps(X, phi, bias, alpha, iters, eps, clamp, norm_eps):
    """The three maps of a hyper-connection from the streams X [n, ..., C]
    (mHC, arXiv:2512.24880): (H_pre [..., n], H_post [..., n], H_res
    [..., n, n]), all f32.

        x~    = rms(vec(X)), no learned scale             [..., n*C]
        pre   = a_pre  * (x~ phi[:, :n])    + b[:n]
        post  = a_post * (x~ phi[:, n:2n])  + b[n:2n]
        res   = a_res  * (x~ phi[:, 2n:])   + b[2n:]      as [n, n]
        H_pre = sigmoid(pre);  H_post = 2 sigmoid(post)
        H_res = sinkhorn(exp(clip(res, clamp)))

    phi [n*C, 2n + n*n] with vec(X) stream-major, bias [2n + n*n], alpha
    [3] = (a_pre, a_post, a_res). The projection runs in X's dtype with f32
    accumulation; everything after it is f32. The norm's factor is a scalar
    a token, so it multiplies the PROJECTION and x~ is never made: the sum
    of squares and X phi come from one read of the streams
    (`ops.hc_stat`), and carry the name under which a recomputation keeps
    them (`_keep_hc_stat`)."""
    from horovod_tpu.ops.hc_stat import hc_stat
    n, C = X.shape[0], X.shape[-1]
    sumsq, proj = (checkpoint_name(t, profile.HC_STAT)
                   for t in hc_stat(X, phi))
    raw = lax.rsqrt(sumsq / (n * C) + norm_eps)[..., None] * proj
    bias = bias.astype(jnp.float32)
    a = alpha.astype(jnp.float32)
    pre = a[0] * raw[..., :n] + bias[:n]
    post = a[1] * raw[..., n:2 * n] + bias[n:2 * n]
    res = (a[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(
        raw.shape[:-1] + (n, n))
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(jnp.exp(jnp.clip(res, clamp[0], clamp[1])), iters, eps))


@functools.cache
def _keep_hc_stat():
    """The policy of this module's recomputations (`hc_remat`,
    `block_remat`): they run everything again but what `hc_maps` derives
    from a full pass over the streams, each token's sum of squares and
    its projection (16 KB + 384 KB a connection at 4096 tokens and four
    streams), which they keep by name. A model without hyper-connections
    names nothing, and its recomputation keeps nothing, as ever. ONE object
    for every layer: JAX caches a jitted call's kept and recomputed halves
    by (the call's jaxpr, the policy), so a closure a layer lowers every
    jitted kernel of every recomputed layer anew (a third of the SDAR
    cell's `tpu_custom_call`s, `docs/TRACING.md`)."""
    return jax.checkpoint_policies.save_only_these_names(profile.HC_STAT)


def hc_read(h_pre, X):
    """The branch's input H_pre X [..., C] from the streams X [n, ..., C]:
    a weighted sum over the streams, in f32, as X's dtype."""
    return sum(h_pre[..., i, None] * X[i].astype(jnp.float32)
               for i in range(X.shape[0])).astype(X.dtype)


def hc_write(h_res, h_post, X, y):
    """The next streams H_res X + H_post^T y, [n, ..., C] in X's dtype:
    stream m is sum_i H_res[m, i] X_i + H_post[m] y."""
    n = X.shape[0]
    xf = [X[i].astype(jnp.float32) for i in range(n)]
    yf = y.astype(jnp.float32)
    return jnp.stack([
        (sum(h_res[..., m, i, None] * xf[i] for i in range(n))
         + h_post[..., m, None] * yf).astype(X.dtype) for m in range(n)])


def hc_stats(intermediates):
    """The largest deviation of any H_res's row or column sums from 1 over
    a batch (f32 scalar), from the ``hc_res_sums`` the hyper-connections
    sowed under ``intermediates``: how doubly stochastic the Sinkhorn
    iterations left the maps."""
    from horovod_tpu.parallel.expert import _sown
    found = _sown(intermediates, "hc_res_sums")
    if not found:
        raise ValueError("no HyperConnection sowed into these intermediates")
    return jnp.max(jnp.stack(found))


class HyperConnection(nn.Module):
    """The maps of ONE branch's hyper-connection and the branch's input:
    X [n, B, L, C] -> (h [B, L, C], H_post, H_res) (`hc_maps`, `hc_read`).
    Parameters `phi`, `bias`, `alpha` as `hc_maps` takes them. They start
    where one stream is read, every stream is written and the streams pass
    through unmixed: alpha 0.01, phi small, the bias of `res` a large
    diagonal, of `pre` large on the first stream alone, of `post` zero
    (H_post = 1)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, X):
        cfg = self.cfg
        n, C = cfg.hc_mult, X.shape[-1]

        def bias_init(key, shape, dtype):
            del key
            pre = jnp.full((n,), -8.0).at[0].set(8.0)
            res = jnp.where(jnp.eye(n, dtype=bool), 8.0, -8.0).reshape(-1)
            return jnp.concatenate([pre, jnp.zeros((n,)),
                                    res]).astype(dtype).reshape(shape)

        k = 2 * n + n * n
        phi = self.param("phi", nn.initializers.normal(0.02), (n * C, k),
                         jnp.float32)
        bias = self.param("bias", bias_init, (k,), jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           jnp.float32)
        with jax.named_scope(profile.HC_MAP):
            h_pre, h_post, h_res = hc_maps(
                X, phi, bias, alpha, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                cfg.hc_res_clamp, cfg.norm_eps)
            self.sow("intermediates", "hc_res_sums", jnp.maximum(
                jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)),
                jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1.0))))
        with jax.named_scope(profile.HC_MIX):
            return hc_read(h_pre, X), h_post, h_res


class Attention(nn.Module):
    cfg: TransformerConfig
    # The layer's kind under `attention_types` ("full" | "window"); None:
    # the stack's one kind (causal, or `attention_mask`).
    kind: Optional[str] = None
    # What the kind's layers are beyond their mask (`Layer.shape`); a field
    # it leaves None is the configuration's own.
    shape: Optional[AttentionShape] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        shape = self.shape or AttentionShape()
        mask = cfg.attention_mask
        if self.kind == "window":
            from horovod_tpu.ops import BandMask
            mask = BandMask(cfg.attention_window)
        head_dim = cfg.head_dim or cfg.embed_dim // cfg.num_heads
        H = shape.num_heads or cfg.num_heads
        G = cfg.num_kv_heads or cfg.num_heads
        if H % G:
            raise ValueError(
                "num_kv_heads=%d must divide num_heads=%d" % (G, H))
        base = cfg.rope_base if shape.rope_base is None else shape.rope_base
        yarn = shape.rope_yarn or (cfg.rope_yarn if self.kind == "full"
                                   else None)
        rot = shape.rotary_dim or head_dim

        def rotate(t):
            """The first `rot` channels of each head of t by the layer's
            frequencies; the others as they are."""
            part = t if rot == head_dim else t[..., :rot]
            if yarn is not None:
                # YaRN over the rotated slice: cos and sin both times its
                # factor, so a rotated q.k carries the factor's square.
                m = (yarn_mscale(yarn.factor, yarn.mscale)
                     / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
                part = _rotary_freq(part, positions,
                                    yarn_inv_freq(rot, base, yarn), m)
            else:
                part = _rotary(part, positions, base)
            return part if rot == head_dim else jnp.concatenate(
                [part, t[..., rot:]], axis=-1)

        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, head_dim), dtype=cfg.dtype,
            param_dtype=jnp.float32, use_bias=False, name=name)
        # The work outside the kernels under the profiler's three names
        # (`profile.ATTN_PARTS`): no module and no parameter name.
        with jax.named_scope(profile.ATTN_PROJ):
            q = heads(H, "query")(x)
            k = heads(G, "key")(x)
            v = heads(G, "value")(x)
        if cfg.attention_gate == "head":
            with jax.named_scope(profile.ATTN_GATE):
                gate = jax.nn.sigmoid(nn.Dense(
                    H, dtype=cfg.dtype, param_dtype=jnp.float32,
                    use_bias=False, name="gate")(x).astype(jnp.float32))
        with jax.named_scope(profile.ATTN_NORM):
            if cfg.qk_norm == "head":
                # Over each head's own width: the norm acts on the last
                # axis, its one scale [head_dim] shared by the heads.
                q = _rms_norm(cfg, "q_norm")(q)
                k = _rms_norm(cfg, "k_norm")(k)
            elif cfg.qk_norm:
                def whole(t, name):
                    flat = t.reshape(t.shape[:-2] + (-1,))
                    return _rms_norm(cfg, name)(flat).reshape(t.shape)
                q, k = whole(q, "q_norm"), whole(k, "k_norm")
        with jax.named_scope(profile.ATTN_ROPE):
            if cfg.rotary:
                q, k = rotate(q), rotate(k)
        if cfg.attention == "ring":
            o = ring_attention(q, k, v, cfg.sp_axis, causal=True,
                               schedule=cfg.sp_schedule)
        elif cfg.attention == "ulysses":
            o = ulysses_attention(q, k, v, cfg.sp_axis, causal=True)
        elif cfg.attention == "flash":
            from horovod_tpu.ops import flash_attention
            # The head gate goes INTO the kernels (a scale of a tile's rows:
            # `flash_attention`'s ``gate``); every other branch multiplies
            # below.
            gated = {} if cfg.attention_gate != "head" else {"gate": gate}
            if mask is not None:
                o = flash_attention(q, k, v, mask=mask, **gated)
            else:
                o = flash_attention(q, k, v, causal=True, **gated)
        else:
            if G != H:
                k = jnp.repeat(k, H // G, axis=2)
                v = jnp.repeat(v, H // G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s * (head_dim ** -0.5)
            L = s.shape[-1]
            rows = lax.broadcasted_iota(jnp.int32, (L, L), 0)
            cols = lax.broadcasted_iota(jnp.int32, (L, L), 1)
            seen = rows >= cols if mask is None \
                else mask.visible(rows, cols)
            s = jnp.where(seen[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        if cfg.attention_gate == "head" and cfg.attention != "flash":
            with jax.named_scope(profile.ATTN_GATE):
                o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        with jax.named_scope(profile.ATTN_PROJ):
            out = nn.DenseGeneral(
                cfg.embed_dim, axis=(-2, -1), dtype=cfg.dtype,
                param_dtype=jnp.float32, use_bias=False, name="out")(o)
        if cfg.tp_axis is not None:
            # Each tp shard projected only its local heads: the row-
            # parallel output is a partial sum (Megatron-style).
            out = lax.psum(out, cfg.tp_axis)
        return out


def _conv_init(taps):
    """A depthwise convolution's taps as torch's Conv1d draws them:
    uniform in +-fan_in^-1/2."""
    def init(key, shape, dtype):
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _dt_bias_init(dt_init):
    """The step bias whose softplus is a log-uniform time step: `dt_init` =
    (least, largest, floor)."""
    def init(key, shape, dtype):
        lo, hi, floor = dt_init
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2(nn.Module):
    """A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; Nemotron-H's layer `M`)
    on the normed state u [B, L, D], H heads of P channels in G groups, a
    state of N a channel:

        [z | xBC | dt] = in_proj u        widths HP | HP + 2GN | H
        xBC = silu(conv_causal_depthwise(xBC) + conv_bias)
        [x | B | C] = xBC                 as [H, P] | [G, N] | [G, N]
        dt = softplus(dt + dt_bias);  a = -exp(A_log)          per head
        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        y = rms_G(y * silu(z)) * norm     the mean square over a group's
                                          HP / G channels, gate first
        out = out_proj y

    The recurrence is `ops.ssd.ssd_scan` (chunked); everything after the
    in-projection and before the out-projection is f32. No projection has
    a bias, the convolution has one. Sows ``ssd_state_max`` under
    ``intermediates`` (the largest |S| at a chunk border)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from horovod_tpu.ops.ssd import ssd_scan
        cfg = self.cfg
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner, taps = H * P, cfg.ssm_conv
        conv_dim = inner + 2 * G * N
        B, L, _ = u.shape
        f32 = jnp.float32

        def dense(n, name):
            return nn.Dense(n, dtype=cfg.dtype, param_dtype=f32,
                            use_bias=False, name=name)

        around_zero = _conv_init(taps)
        dt_bias_init = _dt_bias_init(cfg.ssm_dt_init)
        a_log_init = _a_log_init

        # Outside the convolution and the scan the profiler knows two parts
        # (`profile.SSM_PROJ`, `SSM_GATE`): no module, no parameter name.
        with jax.named_scope(profile.SSM_PROJ):
            zxbcdt = dense(inner + conv_dim + H, "in_proj")(u)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = zxbcdt[..., inner + conv_dim:]
        with jax.named_scope(profile.SSM_CONV):
            w = self.param("conv_kernel", around_zero, (taps, conv_dim), f32)
            bias = self.param("conv_bias", around_zero, (conv_dim,), f32)
            # Tap j reads the token taps - 1 - j behind: zeros before the
            # sequence. Padded as it is and widened a tap at a time, so
            # that no f32 copy of the projection is made.
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            xbc = nn.silu(bias + sum(w[j] * padded[:, j:j + L].astype(f32)
                                     for j in range(taps))).astype(cfg.dtype)
        x = xbc[..., :inner].reshape(B, L, H, P)
        b = xbc[..., inner:inner + G * N].reshape(B, L, G, N)
        c = xbc[..., inner + G * N:].reshape(B, L, G, N)
        with jax.named_scope(profile.SSM_GATE):
            dt = jax.nn.softplus(dt.astype(f32) + self.param(
                "dt_bias", dt_bias_init, (H,), f32))
            a = -jnp.exp(self.param("A_log", a_log_init, (H,), f32))
        y, state_max = ssd_scan(x, dt, a, b, c, cfg.ssm_chunk)
        self.sow("intermediates", "ssd_state_max", state_max)
        with jax.named_scope(profile.SSM_GATE):
            skip = self.param("D", nn.initializers.ones, (H,), f32)
            y = y + skip[:, None] * x.astype(f32)
            y = (y.reshape(B, L, inner) * nn.silu(z.astype(f32))).reshape(
                B, L, G, inner // G)
            y = y * lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                + cfg.norm_eps)
            y = (y.reshape(B, L, inner) * self.param(
                "norm", nn.initializers.ones, (inner,), f32)).astype(
                    cfg.dtype)
        with jax.named_scope(profile.SSM_PROJ):
            return dense(cfg.embed_dim, "out_proj")(y)


def ssd_stats(intermediates):
    """The largest |state| any Mamba-2 layer's scan carried over a chunk
    border (f32 scalar), from the ``ssd_state_max`` the mixers sowed under
    ``intermediates``."""
    from horovod_tpu.parallel.expert import _sown
    found = _sown(intermediates, "ssd_state_max")
    if not found:
        raise ValueError("no Mamba2 mixer sowed into these intermediates")
    return jnp.max(jnp.stack(found))


class KimiDeltaAttention(nn.Module):
    """A Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692) on the
    normed state x [B, L, C], H heads of D channels, low ranks of D:

        [q | k | v | f | z | b] = in_proj x   widths HD | HD | HD | D | D | H
        q, k, v = silu(conv_causal_depthwise(.))         no bias
        q = l2(q) D^-1/2;  k = l2(k)                     per head
        g = -exp(A_log) softplus(f_up f + dt_bias)       [H, D] a token, f32
        beta = sigmoid(b)                                per head
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        out = out_proj(rms_D(o) norm * sigmoid(g_up z))  norm [D], the heads'

    The recurrence is `ops.kda.kda_chunked`, the second and third lines
    `ops.kda_conv.kda_qkv`; the convolution, the norms, the decay and the
    gate are f32. No projection and no convolution has a bias; `dt_bias`
    and `A_log` start as `Mamba2`'s. With |k| = 1 and beta in (0, 1) the
    transition is a contraction: nothing is clamped. Sows ``kda_state_max``
    under ``intermediates`` (the largest |S| a chunk ends in)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.kda import kda_chunked
        from horovod_tpu.ops.kda_conv import kda_qkv
        cfg = self.cfg
        H, D, taps = cfg.num_heads, cfg.kda_head_dim, cfg.kda_conv
        inner = H * D
        B, L, _ = x.shape
        f32 = jnp.float32

        def dense(n, name):
            return nn.Dense(n, dtype=cfg.dtype, param_dtype=f32,
                            use_bias=False, name=name)

        around_zero = _conv_init(taps)
        dt_bias_init = _dt_bias_init(cfg.ssm_dt_init)
        a_log_init = _a_log_init

        def heads(t):
            return t.reshape(B, L, H, D)

        with jax.named_scope(profile.KDA_PROJ):
            proj = dense(3 * inner + 2 * D + H, "in_proj")(x)
        f = proj[..., 3 * inner:3 * inner + D]
        z = proj[..., 3 * inner + D:3 * inner + 2 * D]
        b = proj[..., 3 * inner + 2 * D:]
        with jax.named_scope(profile.KDA_CONV):
            # q, k, v [B, L, H D] from the first 3 H D columns of `proj`:
            # the convolutions, SiLU, the l2 norm a head of q and k, q's
            # D^-1/2, the one rounding
            q, k, v = (heads(t) for t in kda_qkv(
                proj, self.param("conv_kernel", around_zero,
                                 (taps, 3 * inner), f32), H, D))
        with jax.named_scope(profile.KDA_PROJ):
            f = dense(inner, "f_up")(f)
            z = dense(inner, "g_up")(z)
        with jax.named_scope(profile.KDA_GATE):
            g = -jnp.exp(self.param("A_log", a_log_init, (H,), f32))[
                :, None] * jax.nn.softplus(heads(
                    f.astype(f32) + self.param("dt_bias", dt_bias_init,
                                               (inner,), f32)))
            beta = jax.nn.sigmoid(b.astype(f32))
        o, _, state_max = kda_chunked(q, k, v, g, beta, cfg.kda_chunk)
        self.sow("intermediates", "kda_state_max", state_max)
        with jax.named_scope(profile.KDA_GATE):
            o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps) * self.param(
                                  "norm", nn.initializers.ones, (D,), f32)
            o = (o * jax.nn.sigmoid(heads(z.astype(f32)))).reshape(
                B, L, inner).astype(cfg.dtype)
        with jax.named_scope(profile.KDA_PROJ):
            return dense(cfg.embed_dim, "out_proj")(o)


def kda_stats(intermediates):
    """The largest |state| any KDA layer's scan ended a chunk in (f32
    scalar), from the ``kda_state_max`` the mixers sowed under
    ``intermediates``."""
    from horovod_tpu.parallel.expert import _sown
    found = _sown(intermediates, "kda_state_max")
    if not found:
        raise ValueError("no KimiDeltaAttention mixer sowed into these "
                         "intermediates")
    return jnp.max(jnp.stack(found))


class GatedShortConv(nn.Module):
    """A double-gated short convolution mixer (LFM2, arXiv:2511.23404;
    `transformers`' `lfm2` / `lfm2_moe` modelling code states the same
    lines) on the normed state x [B, L, C]:

        [B | G | z] = in_proj x            three equal column blocks of C
        c = conv_causal_depthwise(B * z)   `conv_taps` taps a channel, tap
                                           taps - 1 on the current token,
                                           zeros before the sequence; no
                                           activation, no bias
        out = out_proj(G * c)

    The pass between the projections is `ops.sconv.gated_conv`, f32 from
    the widened projection to its one rounding. No projection and no
    convolution has a bias; the taps start as torch's Conv1d draws them."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from horovod_tpu.ops.sconv import gated_conv
        cfg = self.cfg
        C, taps = cfg.embed_dim, cfg.conv_taps

        def dense(n, name):
            return nn.Dense(n, dtype=cfg.dtype, param_dtype=jnp.float32,
                            use_bias=False, name=name)

        with jax.named_scope(profile.SCONV_PROJ):
            proj = dense(3 * C, "in_proj")(x)
        with jax.named_scope(profile.SCONV_GATE):
            y = gated_conv(proj, self.param(
                "conv_kernel", _conv_init(taps), (taps, C), jnp.float32))
        with jax.named_scope(profile.SCONV_PROJ):
            return dense(C, "out_proj")(y)


@jax.custom_vjp
def _hold_cotangent(h):
    """`h`, and in the backward its cotangent held as a value of the
    program (`lax.optimization_barrier`, under `profile.SCONV_HOLD`).

    Between a "conv" layer's norm and its mixer. The cotangent is the
    in-projection's data gradient [B, L, 3 C] x [3 C, C], and the norm's
    backward reads it three ways: for `dx`, for a sum along each of its rows
    and for the scale's gradient, a sum over all of them. Left to itself
    libtpu puts both reductions INTO the product's fusion
    (`multiply_reduce_fusion`, three results); held, the product is a plain
    fusion of one result and the norm's backward a pass of its own over `dh`
    and `x`. On the v5e at [16384, 6144] x [6144, 2048] the joined fusion
    took 2.44 ms in five layers and 2.99 in three, the plain product 2.41
    and the pass 0.09-0.18, and the layers' weight gradients and recomputed
    in-projections lost 3 ms more beside it: 4.1 of 522.8 ms a step
    (`lfm2moe8b_1chip`, PR 66; `PERF.md` s6). Timed alone the two forms
    are equal at every cell's shape (`examples/norm_grad_sweep.py`), so no
    other mixer holds its cotangent. The forward is `h` itself, the
    gradient the same arithmetic, and no memory is held: `dh` was a result
    of the joined form too. ONE function for every layer, for
    `_keep_hc_stat`'s reason."""
    return h


def _hold_cotangent_bwd(_, g):
    with jax.named_scope(profile.SCONV_HOLD):
        return (lax.optimization_barrier(g),)


_hold_cotangent.defvjp(lambda h: (h, None), _hold_cotangent_bwd)


class Block(nn.Module):
    """One block of the stack on x [B, L, C] (the streams [n, B, L, C]
    under `cfg.hc_mult` = n > 1): for each branch of `layer`,
    `x + out_norm(mixer(norm(x)))` or, on streams, the same branch inside
    its hyper-connection, X' = H_res X + H_post^T F(H_pre X), whose work
    lies under `hvd_hc` (`hvd_hc_map`, `hvd_hc_mix` inside)."""
    cfg: TransformerConfig
    layer: Layer = Layer()

    @nn.compact
    def __call__(self, x, positions):
        cfg, layer = self.cfg, self.layer
        streams = cfg.hc_mult > 1
        if streams:
            connection = nn.remat(HyperConnection, policy=_keep_hc_stat()) \
                if cfg.hc_remat else HyperConnection
        for mixer, norm, out_norm in zip(layer.branches, layer.norms,
                                         layer.out_norms):
            f, around, inside = _mixer(cfg, layer, mixer, positions)
            with _scope(around):
                if streams:
                    with jax.named_scope(profile.HC):
                        h, h_post, h_res = connection(
                            cfg, name="hc_mlp" if mixer in ("mlp", "moe")
                            else "hc_attn")(x)
                else:
                    h = x
                h = _rms_norm(cfg, norm)(h)
                if mixer == "conv":
                    h = _hold_cotangent(h)
                with _scope(inside):
                    y = f(h)
                    if out_norm is not None:
                        y = _rms_norm(cfg, out_norm)(y)
                    if streams:
                        with jax.named_scope(profile.HC), \
                                jax.named_scope(profile.HC_MIX):
                            x = hc_write(h_res, h_post, x, y)
                    else:
                        x = x + y
        return x


# Plain functions, not methods: flax names a scope after every method it
# wraps, and the blocks' scope paths stay what they were.

def _scope(name):
    """The profiler's `name` around what follows, where there is one."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _mixer(cfg, layer, mixer, positions):
    """(a branch's mixer as a function of its normed input; the profiler's
    name around the branch, its norm and its add, or None; the name around
    the mixer and the add alone, or None). A layer with a kind has its
    attention half under the kind's name: two kinds share a kernel name and
    a shape, and the trace tells them apart. Flax's module names are `attn`
    for whatever stands in attention's place, `ssm`, `moe_mlp`; `mlp` is
    the profiler's scope for the feed-forward, dense or routed, no module
    and no parameter name."""
    if mixer in ("attn", "latent"):
        module = Attention(cfg, kind=layer.kind, shape=layer.shape,
                           name="attn") \
            if mixer == "attn" else LatentAttention(cfg, name="attn")
        return (lambda h: module(h, positions),
                profile.ATTN_KINDS[layer.kind] if layer.kind else None, None)
    if mixer == "kda":
        return KimiDeltaAttention(cfg, name="attn"), profile.KDA, None
    if mixer == "conv":
        return GatedShortConv(cfg, name="attn"), profile.SCONV, None
    if mixer == "ssm":
        return Mamba2(cfg, name="ssm"), None, profile.SSM
    if mixer in ("mlp", "moe"):
        return lambda h: _feed_forward(cfg, mixer == "moe", h), None, None
    raise ValueError("Layer.branches names %r: attn, latent, kda, ssm, "
                     "conv, mlp or moe" % (mixer,))


def _feed_forward(cfg, moe, h):
    """A block's feed-forward branch on the normed `h`, dense or routed,
    under the profiler's `mlp`."""
    if moe:
        from horovod_tpu.parallel.expert import MoeMlp
        with jax.named_scope("mlp"):
            return MoeMlp(num_experts=cfg.moe_experts,
                          mlp_dim=cfg.moe_dim or cfg.mlp_dim,
                          capacity_factor=cfg.moe_capacity_factor,
                          ep_axis=cfg.ep_axis, ep_size=cfg.ep_size,
                          top_k=cfg.moe_top_k, gated=cfg.moe_gated,
                          renormalize=cfg.moe_renormalize,
                          dtype=cfg.dtype, scoring=cfg.moe_scoring,
                          route_scale=cfg.moe_route_scale,
                          held=cfg.moe_held, shared_dim=cfg.moe_shared_dim,
                          act=cfg.moe_act,
                          shared_gated=cfg.moe_shared_gated,
                          latent_dim=cfg.moe_latent_dim, name="moe_mlp")(h)
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
    return h


class Transformer(nn.Module):
    """tokens [B, L_local] (+ global positions when sequence-sharded) ->
    logits [B, L_local, vocab].

    ``return_hidden=True`` skips the lm_head projection and returns the
    final normed hidden states — pair with
    `horovod_tpu.ops.losses.chunked_softmax_cross_entropy` (and the
    lm_head kernel from the params tree; under ``cfg.tie_embeddings`` the
    embedding table transposed, there being no `lm_head`) to train
    without ever materializing the [B, L, vocab] logits: that loss projects
    a chunk of rows at a time and forms both gradients in the same pass,
    so its peak is O(rows x vocab) plus the [D, vocab] f32 and [B, L, D]
    gradients it hands to the backward.

    With ``cfg.num_passes`` > 1 the blocks run that many times on the same
    weights and the logits are the last pass's. With ``cfg.exit_gate``,
    ``return_hidden=True`` returns ``(hidden [T, B, L, D], gate logits
    [T, B, L] f32)`` of all T passes: what
    `horovod_tpu.ops.losses.expected_exit_loss` takes.

    With ``cfg.hc_mult`` = n > 1 the embedding fills n residual streams, the
    blocks run on [n, B, L, C] and the streams' sum goes to `norm_f`. With
    ``cfg.mtp_depth`` = 1, ``return_hidden=True`` returns ``(hidden,
    hidden_mtp)``, both [B, L, D]: the stack's normed state, for the next
    token, and the prediction module's, for the token after it (the module:
    `mtp_proj` on [`mtp_norm_h`(the stack's state before `norm_f`) |
    `mtp_norm_e`(the NEXT token's embedding, the sequence closed on
    itself)], one more block `mtp_block`, `mtp_norm_f`; the same embedding
    and head)."""
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
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                         param_dtype=jnp.float32, dtype=cfg.dtype,
                         name="embed")
        with jax.named_scope(profile.EMBED):
            embedded = embed(tokens)
        table = cfg.layers()
        blocks = [
            (nn.remat(Block, policy=_keep_hc_stat()) if layer.remat
             else Block)(cfg, layer, name="block_%d" % i)
            for i, layer in enumerate(table[:cfg.num_layers])]
        norm_f = _rms_norm(cfg, "norm_f")
        n = cfg.hc_mult

        def fill(h):   # the streams start as copies
            return jnp.broadcast_to(h, (n,) + h.shape) if n > 1 else h

        def close(X):  # and end as their sum
            return jnp.sum(X, axis=0, dtype=jnp.float32).astype(X.dtype) \
                if n > 1 else X

        # The loop's names exist only where there is a loop.
        looped = cfg.num_passes > 1
        # The same blocks and the one final norm, `num_passes` times: a
        # pass's normed output is its exit's hidden state and the next
        # pass's input. Unrolled, so that every pass keeps its own name in
        # the profiler's trace.
        x, exits = embedded, []
        with _scope(profile.LOOP if looped else None):
            for t in range(cfg.num_passes):
                with _scope(profile.LOOP_PASS % (t + 1) if looped
                            else None):
                    X = fill(x)
                    for block in blocks:
                        with jax.named_scope(profile.BLOCK):
                            X = block(X, positions)
                    with jax.named_scope(profile.HEAD):
                        last = close(X)
                        x = norm_f(last)
                exits.append(x)
        hidden = x
        # The gate and the module are formed in either mode, so that `init`
        # makes them beside the head; unused, they are no part of the
        # program.
        if cfg.exit_gate:
            with jax.named_scope(profile.EXIT):
                # In f32: a matrix of one column, and its logits decide
                # every exit's weight in the loss.
                stacked = jnp.stack(exits)
                hidden = stacked, nn.Dense(
                    1, dtype=jnp.float32, param_dtype=jnp.float32,
                    name="exit_gate")(stacked)[..., 0]
        if cfg.mtp_depth:
            with jax.named_scope(profile.MTP):
                # The NEXT token's embedding is the embedded sequence turned
                # by one position: no second lookup.
                e_next = jnp.roll(embedded, -1, axis=1)
                h = nn.Dense(cfg.embed_dim, dtype=cfg.dtype,
                             param_dtype=jnp.float32, use_bias=False,
                             name="mtp_proj")(jnp.concatenate(
                                 [_rms_norm(cfg, "mtp_norm_h")(last),
                                  _rms_norm(cfg, "mtp_norm_e")(e_next)],
                                 axis=-1))
                with jax.named_scope(profile.BLOCK):
                    X = Block(cfg, table[-1], name="mtp_block")(
                        fill(h), positions)
                hidden = x, _rms_norm(cfg, "mtp_norm_f")(close(X))
        if return_hidden:
            return hidden
        with jax.named_scope(profile.HEAD):
            if cfg.tie_embeddings:  # the table transposed: no `lm_head`
                logits = embed.attend(x)
            else:
                logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                                  param_dtype=jnp.float32, use_bias=False,
                                  name="lm_head")(x)
            return logits.astype(jnp.float32)
