"""Flash attention as a Pallas TPU kernel.

The three plain kernels (forward, dQ, dK/dV) are one algorithm in two
forms, and `flash_plan` chooses between them per kernel from the call's
shapes alone (L, D, the head group, the dtype) — no argument, no
environment variable:

- resident, when the whole-sequence operands fit a VMEM budget (forward
  and dQ: k and v; dK/dV: q, dO, lse and delta), which at D=128 and one
  head a kv head is every kernel up to L=8192, and forward and dQ at
  16384. Grid (batch*kv_head, q-block) — (batch*kv_head,
  k-block) for dK/dV. Where dQ's block and an f32 accumulator of dQ's
  shape fit beside dK/dV's operands (D=128: up to L=4096) the backward
  is ONE kernel, `hvd_flash_bwd`: the dK/dV kernel, adding each tile's
  ds.k to dQ's rows as it goes, so s, p, dp and ds are formed once a
  tile (5 matmuls and one exp where the two kernels make 7 and two);
  its k-block axis carries dQ and runs in order.
  The one kernel has a second held side for the calls whose head group
  makes q and dO too large (3 KiB a row of the grouped layout, a row a
  position and query head): held by the q block, on dQ's grid
  (batch*kv_head, q-block) and walk, with k, v, dk and dv whole in VMEM,
  dQ of the block carried by the loop and dK and dV summed over the q
  blocks in two f32 [L, D] accumulators in scratch (3 KiB a position
  whatever the group), rounded and written at the (batch, kv head)'s
  last q block; its q-block axis runs in order.
  The other sequence comes in as ONE block per
  batch*kv_head: its block index does not change across the inner grid
  axis, so Pallas fetches it once and double-buffers the next one behind
  this one's work. The kernel walks its row (column) of the causal
  triangle itself: a `fori_loop` over the blocks wholly below the
  diagonal with no mask, then the one or two blocks that straddle it,
  peeled, masked unconditionally. The online-softmax state (acc,
  running max, running sum; dq; dk and dv) is carried by the loop, not
  stored to and loaded from scratch on every tile, and no tile above
  the diagonal costs anything. A grid step costs 0.43 us on the v5e
  and the gridded form pays one per tile: at L=2048 that was 44% of
  the kernels' time (PERF.md, PR 28).
- gridded, beyond the budget (what the ring-step kernels always are): a
  (batch*kv_head, q-block, k-block) grid, k innermost and sequential.
  Each step's k/v block is streamed HBM->VMEM by the
  Pallas pipeline (double-buffered against the MXU work of the previous
  block), while the online-softmax state lives in VMEM scratch that
  persists across the k steps of one q block — the standard TPU flash
  recipe. Causal masking skips both the compute (`pl.when`) and the
  fetch (index maps clamp above-diagonal steps to the frontier block;
  Pallas elides the DMA for a revisited block index) of k-blocks above
  the diagonal — at long L this halves attention HBM traffic.

Both forms visit the same tiles in the same order with the same
arithmetic: 128-aligned blocks, bf16 operands into every matmul, f32
accumulation and f32 softmax.

GQA/MQA (num_kv_heads < num_heads) uses a grouped-rows layout: the
`group = H / G` query heads sharing one kv head are interleaved into the
q rows (row r of kv-head g's [L*group, D] slab is position r//group,
head g*group + r%group). One kv block then serves the whole group per
fetch, k/v is never materialized at H heads (the HBM win that motivates
GQA), and dK/dV accumulate the group reduction inside the kernel instead
of a [B,H,L,D] gradient plus a post-hoc sum. The only kernel change is
that row positions are `row // group` — masks, frontier clamps and
block-skip predicates all run in position units.

Rotary embedding is the caller's (`models.transformer._rotary`, from the
caller's positions): the kernels take q and k as they are scored.

Scores of two products (`q_shared`, `k_shared`: latent attention's
rotary slice): s = scale * (q.k^T + q2.k2^T) with k2 ONE key a position for
every head, never broadcast in memory: its block's index is the batch's, so
consecutive heads of a batch reuse the fetched block. Every kernel takes the
second pair beside the first, in whichever form `flash_plan(...,
shared_dim=64)` gives it: at D=128, D2=64 the forward resident up to
L=16384 and the backward ONE kernel, held by the k block at L=4096 and by
the q block at L=8192. Held by the q block the whole-sequence operands (k,
v, k2, dk, dv, dk2) have ONE pipeline buffer each (a head's k and v wait
for their copy once, 4 MiB against half a millisecond of work), and the
shared key's gradient is summed over the heads where it is formed, in a
third f32 accumulator that lives across all the heads of a batch: no
head's part of it reaches HBM. From the kernels held by the k block and the
gridded ones it leaves a head at a time and is summed outside.

A mask by RULE over (query position, key position) (`mask=`; the rules
are `BlockDiffusionMask` and `BandMask`, the causal band of sliding-window
attention): the rule says, for a tile of queries, which
runs of key tiles hold a visible pair and which of those need the mask
pass, as closed forms in the tile's position (`key_runs`, `query_runs`). The
resident kernels loop over those runs alone (a tile the rule empties is
never computed, a tile it fills runs with no mask pass, a tile it cuts is
masked by `visible`); the gridded dK/dV kernel gates the compute by the same
runs and clamps its q-side block index into them, so an empty tile is not
fetched either. The resident kernels held by the q block walk a rule's
three runs, mostly a tile or two long, so as to enter few loops: the forward
and dQ, whose loops carry their state, take the runs of a kind in ONE loop
(`_walk_runs_merged`); the one-kernel backward sums dQ of its block in VMEM
scratch beside dK and dV, its loops carry nothing, and it asks the rule for
its runs at a FINER key step too (`cut_k`, `_cut_k`): a cut k block of which
the rule leaves one sub-tile in sight is a turn of that sub-tile
(`_walk_cut_runs`). `flash_plan(..., mask=)` counts the tiles, and the
sub-tiles, from the same runs. The forward and dQ take a rule in their
resident form only (k and v whole in VMEM: at D=128 up to 24576 positions);
where that does not fit the call is the blockwise jnp form.

Backward: custom VJP over saved per-row log-sum-exp (FlashAttention-2
style). On non-TPU backends the same kernels run in Pallas interpret
mode (tests) or fall back to the blockwise JAX implementation.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu import profile

BLOCK_Q = 128
BLOCK_K = 128


def _to_rows(x, group):
    """[B, H, L, D] (H = G*group) -> grouped kernel layout
    [B*G, L*group, D], row = pos*group + u for head g*group + u."""
    B, H, L, D = x.shape
    G = H // group
    return (x.reshape(B, G, group, L, D).transpose(0, 1, 3, 2, 4)
            .reshape(B * G, L * group, D))


def _from_rows(x, B, group):
    """Inverse of `_to_rows`: [B*G, L*group, D] -> [B, G*group, L, D]."""
    BG, R, D = x.shape
    G = BG // B
    L = R // group
    return (x.reshape(B, G, L, group, D).transpose(0, 1, 3, 2, 4)
            .reshape(B, G * group, L, D))


def _scores(q, k, scale):
    """s = (q.k^T)*scale, f32 [BQ, BK], from native-dtype operands."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _scores2(q, k, q2, k2, scale):
    """s = (q.k^T + q2.k2^T)*scale: the scores of two products."""
    dims = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(q, k, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(q2, k2, dims,
                                  preferred_element_type=jnp.float32)) * scale


def _causal_mask(s, q_off, kv_off, fill, group=1):
    """Causal masking of a score block by global positions: q row r is
    position q_off + r//group (grouped GQA layout; group=1 is the plain
    layout), column c is position kv_off + c."""
    block_q, block_k = s.shape
    riota = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    rows = q_off + (riota // group if group > 1 else riota)
    cols = kv_off + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows >= cols, s, fill)


def _masked_scores(q, k, scale, causal, q_off, kv_off, fill, group=1,
                   shared=None):
    """Scores of one grid step with causal masking. Only blocks
    straddling the diagonal pay the elementwise mask pass (the kernels
    are VPU-bound, every pass counts); `fill` is -inf for scores, 0 for
    probabilities. ``shared``: (q2, k2), the step's blocks of a second
    score product (`_scores2`)."""
    s = _scores(q, k, scale) if shared is None \
        else _scores2(q, k, *shared, scale)  # [BQ, BK]
    if not causal:
        return s
    # q_off is the POSITION of the block's first row.
    straddles = kv_off + (k.shape[0] - 1) > q_off
    return jax.lax.cond(
        straddles,
        lambda s: _causal_mask(s, q_off, kv_off, fill, group),
        lambda s: s, s)


def _online_softmax_update(s, v_ref, acc_ref, m_ref, l_ref, guard_empty):
    """One online-softmax block update of the (acc, m, l) state refs.
    `guard_empty` handles rows no block has touched yet (m == -inf, the
    ring-step case where visitation order is data-dependent); the plain
    forward's ascending k order makes the first visible block cover
    every row, so it skips the two extra passes."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if guard_empty:
        alpha = jnp.where(jnp.isneginf(m_new), 0.0, alpha)
        p = jnp.where(jnp.isneginf(m_new), 0.0, p)
    l_ref[...] = jnp.broadcast_to(
        l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_kernel(*refs, scale, causal, num_kb, bqp, group, shared=False):
    # q_ref: [BQ, D]; k_ref/v_ref: [BK, D]; o_ref: [BQ, D];
    # scratch: acc [BQ, D] f32, m/l [BQ, 128] f32 (state across k steps).
    # bqp = BQ // group: positions per q block (grouped GQA).
    # `shared`: q2_ref [BQ, D2] and k2_ref [BK, D2] follow v (`_scores2`).
    second = None
    if shared:
        second = refs[3:5]
        refs = refs[:3] + refs[5:]
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_k = k_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: skip the compute (the fetch is pipelined regardless) of
    # k-blocks entirely above the diagonal. Position units.
    visible = (kj * block_k < (qi + 1) * bqp) if causal else kj >= 0

    @pl.when(visible)
    def _compute():
        # Matmuls take the inputs' native (bf16) dtype — the MXU's fast
        # path — and accumulate in f32; only softmax runs in f32.
        s = _masked_scores(q_ref[...], k_ref[...], scale, causal,
                           q_off=qi * bqp, kv_off=kj * block_k,
                           fill=-jnp.inf, group=group,
                           **({"shared": (second[0][...], second[1][...])}
                              if shared else {}))
        _online_softmax_update(s, v_ref, acc_ref, m_ref, l_ref,
                               guard_empty=False)

    @pl.when(kj == num_kb - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible keys
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        # Log-sum-exp per row, saved for the backward recompute.
        lse_ref[...] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                        lse_ref.shape)


def _pick_block(L, preferred):
    for b in (preferred, 512, 256, 128):
        if b <= preferred and L % b == 0:
            return b
    return None


def _pick_rows_block(L, preferred, group):
    """Row-block size. group=1: the plain picker. Grouped GQA layouts
    pick `bqp` positions * `group` interleaved head rows with bqp | L
    and total rows at most the preference (for grouped layouts that is
    `_grouped_blocks`' row cap, swept separately from the plain row
    budgets); bqp >= 8 keeps the resulting rows a sublane multiple for
    any group."""
    if group == 1:
        return _pick_block(L, preferred)
    for bqp in (512, 256, 128, 64, 32, 16, 8):
        if bqp * group <= preferred and L % bqp == 0:
            return bqp * group
    return None


def _grouped_blocks(D, L, group, backward=False):
    """(rows_cap, block_k) for grouped-GQA layouts. v5e sweeps
    (examples/flash_block_sweep.py --G N) at L=8192: grouped blocks
    want MORE rows and a NARROWER k block than the plain policy —
    D=128 group=3: fwd 1536/512 beats the plain-cap 384/512 by 10%
    AND plain MHA itself by 1.4%; bwd 1536/512 is 22% under the
    plain-cap pick and 19% under plain MHA (the in-kernel dK/dV group
    reduction writes G instead of H heads). D=64 group=4: 2048/512
    beats the plain-cap 512/1024 by 6% fwd / 10% fwd+bwd (2048/1024
    overflows VMEM — s alone is 8 MB f32). Shapes without sweep data
    (short L) keep the conservative plain-preference cap.

    Interpolation caveat for UNSWEPT group sizes: the caps above were
    measured at group=4 (D<=64 -> 2048 rows) and group=3 (D>64 -> 1536
    rows) only, and are applied to every group>1 at long L. For other
    groups the power-of-two bqp search in _pick_rows_block then lands
    on smaller row blocks than the cap suggests (e.g. group=2, D=64:
    bqp=512 -> 1024 rows, not 2048) — a performance-only divergence
    from a hypothetical per-group optimum, never a correctness issue
    (_check_blocks still enforces exact tiling). Extend the sweep
    (examples/flash_block_sweep.py --G N) before trusting these caps
    for a new production group size."""
    pq, pk = _default_blocks(D, L, backward)
    long_seq = L is not None and L >= 4096
    if group > 1 and long_seq:
        cap = 1536 if D > 64 else 2048
        return cap, (512 if L % 512 == 0 else pk)
    return pq, pk


def _default_blocks(D, L=None, backward=False):
    """Preferred (block_q, block_k) by head dim and sequence length,
    from v5e sweeps (examples/flash_block_sweep.py): (256, 512) at
    D=128; D<=64 leaves VMEM headroom for wider blocks — (256, 1024)
    forward / (512, 1024) backward at L=2048. Long sequences amortize
    still-bigger q blocks (L=8192 sweep: fwd (512,1024) 8.95 vs 10.39
    ms/layer, bwd (1024,1024) ~15.7 vs ~17.1): at L>=4096 the q block
    doubles. ONE definition for the plain and ring paths so a retune
    can't leave them inconsistent."""
    long_seq = L is not None and L >= 4096
    if D <= 64:
        if backward:
            return (1024, 1024) if long_seq else (512, 1024)
        return (512, 1024) if long_seq else (256, 1024)
    # D=128 at L=8192: fwd (512,512) 6.12 vs 8.24 ms/layer for the
    # L=2048-swept (256,512); bwd (512,1024) ~8.2 vs ~10.3.
    if long_seq:
        return (512, 1024) if backward else (512, 512)
    return (256, 512)


def _kv_index_map(bqp, bk, causal):
    """k/v BlockSpec index map for grids with k innermost (position
    units: bqp = positions per q block). Causal runs clamp the k-block
    index to the diagonal frontier: steps above the diagonal revisit
    the frontier block, and Pallas skips the DMA for a revisited index
    — halving k/v HBM traffic at long L (the compute is separately
    gated by `pl.when(visible)`)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b, jnp.minimum(j, ((i + 1) * bqp - 1) // bk), 0)


def _q_index_map(bqp, bk, causal):
    """q-side BlockSpec index map for the dk/dv grid (q innermost).
    Causal runs clamp the q-block index UP to the first block at or
    below the diagonal (qi_min = (kj*bk)//bqp, position units): the
    leading invisible steps revisit that block, skipping their DMA."""
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (b, jnp.maximum(i, (j * bk) // bqp), 0)


def _rule_q_index_map(rule, bqp, bk, positions):
    """`_q_index_map` for a rule: the q-block index clamped into the runs
    of the step's k block (`_clamp_to_runs`)."""
    return lambda b, j, i: (
        b, _clamp_to_runs(i, rule.query_runs(j * bk, bk, bqp, positions)),
        0)


def _require_rows_block(L, preferred, group, what):
    b = _pick_rows_block(L, preferred, group)
    if b is None:
        raise ValueError(
            f"{what}={L} must be a multiple of 128 (or of 8*group for "
            f"grouped kv heads, group={group}) for the Pallas ring "
            f"kernels; pad the sequence shard or use the jnp ring path")
    return b


def _check_blocks(rows, L, bq, bk, group):
    """Fail loudly on block sizes that do not tile the arrays: a
    Pallas grid of rows//bq steps silently TRUNCATES coverage when bq
    does not divide the row count (observed in a block sweep — wrong
    results that look fast)."""
    if not bq or not bk or rows % bq or L % bk or bq % group:
        raise ValueError(
            f"invalid flash blocks: block_q={bq} must divide "
            f"rows={rows} and be a multiple of group={group}; "
            f"block_k={bk} must divide the kv length {L}")


# --- a mask by rule --------------------------------------------------------
#
# `causal=True` is one rule, written into the kernels before there were
# others (`_causal_mask`, `_walk_k_blocks`, `_walk_q_blocks`,
# `_kv_index_map`), and stays as it is. Any other mask is an object with
# three methods, all closed forms in positions, so that the kernels need no
# table: `visible(rows, cols)` elementwise, and `key_runs` / `query_runs`,
# which give for one tile of queries (keys) the RUNS of key (query) tiles
# that hold a visible pair: (first tile, one past the last, whether the run
# needs the mask pass), ascending, a fixed number of them, empty where
# first >= end. The runs are evaluated with `xp=jnp` on the kernel's program
# ids and with `xp=np` on all tiles at once by `flash_plan`. `query_runs` is
# told the call's positions too: where the last viewer of a key lies is the
# sequence's end, which a rule need not carry.
#
# What a rule owes the PLAN, said once for all of them: `tiled(positions)`,
# the length a kernel's blocks must divide (the call's positions, or a part
# of them no tile may straddle), and `check(positions, bqp, bk)`, which
# refuses a call or a pair of blocks the rule does not describe.

def _cdiv(a, n):
    return (a + n - 1) // n


class BlockDiffusionMask(collections.namedtuple("BlockDiffusionMask",
                                                "length block")):
    """The attention mask of block-diffusion training (BD3-LM, Arriola et
    al., arXiv:2503.09573): the sequence is [x_t ; x_0], a NOISY copy at
    positions 0..length-1 and the CLEAN one at length..2*length-1, both in
    blocks of `block` tokens. With N(i) = i < length and B(i) = (i mod
    length) // block, query i sees key j iff

        (N(i) and N(j) and B(i) == B(j))          noisy on its own block
        or (N(i) and not N(j) and B(j) < B(i))    noisy on earlier clean
        or (not N(i) and not N(j) and B(j) <= B(i))   clean, block-causal

    No row is empty. `block` divides `length`; a kernel's tiles must divide
    `length` too (none straddles the halves)."""
    __slots__ = ()

    def _local(self, pos, xp):
        """(is noisy, the block's index within its half) of positions."""
        noisy = pos < self.length
        return noisy, xp.where(noisy, pos, pos - self.length) // self.block

    def visible(self, rows, cols, xp=jnp):
        """Elementwise: whether the query at `rows` sees the key at `cols`
        (int arrays that broadcast; the kernels pass [R, 1] and [1, C], so
        that all but three passes run on thin vectors)."""
        noisy_q, bq = self._local(rows, xp)
        noisy_k, bk = self._local(cols, xp)
        # As two comparisons of integer codes and one `or` (Mosaic has no
        # select between masks): a noisy key is seen by the noisy queries
        # of its block; a clean key where B(j) < B(i) for a noisy query,
        # <= for a clean one.
        own = xp.where(noisy_k, bk, -2) == xp.where(noisy_q, bq, -1)
        reach = xp.where(noisy_q, bq, bq + 1)
        return own | (xp.where(noisy_k, 2 ** 30, bk) < reach)

    def key_runs(self, q_lo, n, bk, xp=jnp):
        """The key tiles (of `bk` positions) that the queries [q_lo, q_lo +
        n) see, as three runs: the noisy tiles of their own blocks (masked),
        the clean tiles every one of them sees whole, the clean tiles some
        of them see in part (masked)."""
        L, b = self
        noisy = q_lo < L
        a = xp.where(noisy, q_lo, q_lo - L)
        first, last = a // b * b, (a + n - 1) // b * b  # the blocks' starts
        own_lo = first // bk
        own_hi = xp.where(noisy, _cdiv(last + b, bk), own_lo)
        all_hi = xp.where(noisy, first, first + b)   # clean keys below this
        any_hi = xp.where(noisy, last, last + b)     # are seen by all / some
        return ((own_lo, own_hi, True),
                (L // bk, (L + all_hi) // bk, False),
                ((L + all_hi) // bk, _cdiv(L + any_hi, bk), True))

    def query_runs(self, k_lo, n, bqp, positions, xp=jnp):
        """The query tiles (of `bqp` positions) that see the keys [k_lo,
        k_lo + n), as five runs: of noisy keys, the noisy tiles of their
        own blocks (masked); of clean keys, the noisy tiles that see some of
        them (masked) and all of them, then the clean tiles likewise.
        `positions` is 2 x length (`check`) and says nothing new."""
        L, b = self
        noisy = k_lo < L
        c = xp.where(noisy, k_lo, k_lo - L)
        first, last = c // b * b, (c + n - 1) // b * b
        own_lo = first // bqp
        own_hi = xp.where(noisy, _cdiv(last + b, bqp), own_lo)
        half, end = L // bqp, 2 * L // bqp

        def clean(t):  # nothing of a noisy key's tile: an empty run at end
            return xp.where(noisy, end, t)

        return ((own_lo, own_hi, True),
                (clean((first + b) // bqp), clean(_cdiv(last + b, bqp)),
                 True),
                (clean(_cdiv(last + b, bqp)), clean(half), False),
                (clean((L + first) // bqp), clean(_cdiv(L + last, bqp)),
                 True),
                (clean(_cdiv(L + last, bqp)), end, False))

    def tiled(self, positions):
        """A half: no tile may straddle the noisy and the clean copy."""
        return self.length

    def check(self, positions, bqp, bk):
        """Refuses a call this rule does not describe."""
        L, b = self
        if L % b or positions != 2 * L:
            raise ValueError(
                "%r wants blocks that divide its length and 2 x length = %d "
                "positions, not %d" % (self, 2 * L, positions))
        if L % bqp or L % bk:
            raise ValueError(
                "flash blocks of %d query and %d key positions must divide "
                "%r's length: no tile may straddle the two halves"
                % (bqp, bk, self))


class BandMask(collections.namedtuple("BandMask", "window")):
    """The causal band of sliding-window attention: query i sees key j iff

        j <= i  and  i - j < window

    itself and the `window` - 1 keys before it. A window of the sequence's
    length or more is the causal triangle. A tile of queries sees a run of
    key tiles cut at the band's lower edge, whole tiles between, and a run
    cut on the diagonal; any blocks that tile the sequence will do."""
    __slots__ = ()

    def visible(self, rows, cols, xp=jnp):
        """Elementwise, as `BlockDiffusionMask.visible`: two comparisons
        against thin vectors and one `and`."""
        return (cols <= rows) & (cols > rows - self.window)

    @staticmethod
    def _runs(lo, whole_lo, whole_hi, hi, xp):
        """[lo, hi) as a cut run, the whole tiles [whole_lo, whole_hi)
        where there are any, and a cut run."""
        whole_lo = xp.minimum(xp.maximum(whole_lo, lo), hi)
        whole_hi = xp.minimum(xp.maximum(whole_hi, whole_lo), hi)
        return ((lo, whole_lo, True), (whole_lo, whole_hi, False),
                (whole_hi, hi, True))

    def key_runs(self, q_lo, n, bk, xp=jnp):
        """The key tiles (of `bk` positions) that the queries [q_lo, q_lo +
        n) see: from the first query's oldest key to the last query's own.
        Every one of them sees a tile whole that begins after the last
        query's oldest key and ends at or before the first query."""
        w = self.window
        return self._runs(
            xp.maximum(q_lo - w + 1, 0) // bk,
            _cdiv(xp.maximum(q_lo + n - w, 0), bk), (q_lo + 1) // bk,
            (q_lo + n - 1) // bk + 1, xp)

    def query_runs(self, k_lo, n, bqp, positions, xp=jnp):
        """The query tiles (of `bqp` positions) that see the keys [k_lo,
        k_lo + n): from the first key's own to the last key's latest
        viewer, or the sequence's end. A tile sees every one of them whole
        that begins at or after the last key and ends within the first
        key's window."""
        w = self.window
        return self._runs(
            k_lo // bqp, _cdiv(k_lo + n - 1, bqp), (k_lo + w) // bqp,
            xp.minimum(_cdiv(k_lo + n + w - 1, bqp), positions // bqp), xp)

    def tiled(self, positions):
        """The whole call: the band has no seam."""
        return positions

    def check(self, positions, bqp, bk):
        if self.window < 1:
            raise ValueError("%r: a query sees itself at least" % (self,))


def _rule_mask(s, rule, q_off, kv_off, fill, group=1):
    """`_causal_mask` for a rule: rows and columns as thin vectors."""
    block_q, block_k = s.shape
    riota = lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    rows = q_off + (riota // group if group > 1 else riota)
    cols = kv_off + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return jnp.where(rule.visible(rows, cols), s, fill)


def _walk_runs(visit, carry, runs):
    """``visit(j, carry, masked)`` over the tiles of `runs`, a loop a run:
    what `_walk_k_blocks` and `_walk_q_blocks` are to the causal rule."""
    for lo, hi, masked in runs:
        carry = lax.fori_loop(
            lo, hi, lambda j, c, masked=masked: visit(j, c, masked), carry)
    return carry


def _runs_by_kind(runs):
    """`runs` as at most two: (masked, how many tiles, the tile at turn t),
    the whole runs' tiles laid end to end, ascending, then the masked runs'
    (the tile at a turn: a chain of selects over the runs of the kind)."""
    for kind in (False, True):
        group = [(lo, hi) for lo, hi, masked in runs if masked == kind]
        if not group:
            continue
        counts = [jnp.maximum(hi - lo, 0) for lo, hi in group]
        starts = [sum(counts[:r]) for r in range(len(group))]

        def tile(t, group=group, counts=counts, starts=starts):
            j = group[-1][0] + t - starts[-1]
            for r in reversed(range(len(group) - 1)):
                j = jnp.where(t < starts[r] + counts[r],
                              group[r][0] + t - starts[r], j)
            return j

        yield kind, sum(counts), tile


def _walk_runs_merged(visit, carry, runs):
    """`_walk_runs` with ONE loop over the tiles of all whole runs, then one
    over those of all masked runs (`_runs_by_kind`). For a kernel whose
    loops CARRY its state (the forward's accumulator, running max and sum:
    1.5 MiB of values that live in VMEM; dQ's): on the v5e every loop such a
    kernel enters costs it about 0.85 us whatever it runs, as much as half a
    tile, and a rule's three runs are mostly a tile or two long
    (`_resident_blocks`). A row's keys are then summed whole tiles first;
    every masked turn keeps the guard for a row it leaves empty."""
    for masked, count, tile in _runs_by_kind(runs):
        carry = lax.fori_loop(
            0, count, lambda t, c, masked=masked, tile=tile: visit(
                tile(t), c, masked), carry)
    return carry


def _seen_of_tile(j, fine, ratio, xp=jnp):
    """Of k block `j`, a whole number `ratio` of sub-tiles: how many of its
    sub-tiles the runs `fine` (in sub-tile units) hold, and the first of
    them."""
    count, first = 0, 2 ** 30
    for lo, hi, _ in fine:
        a = xp.maximum(lo, j * ratio)
        b = xp.minimum(hi, (j + 1) * ratio)
        count = count + xp.maximum(b - a, 0)
        first = xp.where(b > a, xp.minimum(first, a), first)
    return count, first


def _walk_cut_runs(visit, runs, fine, bk, cut_k):
    """`_walk_runs_merged` for a kernel that can take a key tile at any
    sub-tile of the k block (`cut_k` keys, a whole number of them a k block;
    k and v whole in VMEM) and whose loops carry NOTHING (its sums are in
    VMEM scratch): ``visit(j, masked, width)`` at SUB-tile `j`, of `width`
    keys, a k block's or a sub-tile's. `runs` are the rule's in k blocks,
    `fine` the same rule's in sub-tiles. A whole k block is a turn. So is a
    cut one, but where the rule leaves ONE of its sub-tiles in sight (`fine`
    holds no other of it): that turn is of the sub-tile alone, its share of
    every product and pass. A k block with more in sight stays one turn: on
    the v5e a turn of 128 keys costs three quarters of one of 512 (a turn's
    own cost: `_resident_blocks`). Three traced bodies: whole, cut, lone."""
    ratio = bk // cut_k

    def loop(lo, hi, body):
        lax.fori_loop(lo, hi, lambda j, c: body(j) or c, 0)

    for masked, count, tile in _runs_by_kind(runs):
        if not masked:
            loop(0, count, lambda t, tile=tile: visit(tile(t) * ratio, False,
                                                      bk))
            continue

        def turn(t, tile=tile):
            j = tile(t)
            seen, first = _seen_of_tile(j, fine, ratio)
            alone = (seen == 1).astype(jnp.int32)
            loop(0, alone, lambda _: visit(first, True, cut_k))
            loop(alone, 1, lambda _: visit(j * ratio, True, bk))

        loop(0, count, turn)


def _in_runs(i, runs, masked_only=False):
    """Whether tile `i` lies in one of `runs` (in a masked one)."""
    hit = i < 0
    for lo, hi, masked in runs:
        if masked or not masked_only:
            hit = hit | ((lo <= i) & (i < hi))
    return hit


def _clamp_to_runs(i, runs):
    """The tile a gridded step fetches: its own where a run holds it, else
    the next visited one (so it is there when its step comes), else the last
    visited. A revisited index costs no DMA."""
    big = 2 ** 30
    nxt, last = big, 0
    for lo, hi, _ in runs:
        nxt = jnp.minimum(nxt, jnp.where((hi > lo) & (hi > i),
                                         jnp.maximum(lo, i), big))
        last = jnp.maximum(last, jnp.where(hi > lo, hi - 1, 0))
    return jnp.where(nxt < big, nxt, last)


def _rule_tiles(rule, held, positions, bqp, bk):
    """(visited, masked, skipped) score tiles of ONE (batch, kv head) of a
    kernel under `rule`, from the runs the kernel walks: the query tiles of
    each k block where it holds a k block (`held` "k"), else the key tiles
    of each q block."""
    if held == "k":
        runs = rule.query_runs(np.arange(0, positions, bk), bk, bqp,
                               positions, np)
    else:
        runs = rule.key_runs(np.arange(0, positions, bqp), bqp, bk, np)
    def tiles(masked_only):
        return int(sum(np.sum(np.maximum(hi - lo, 0))
                       for lo, hi, masked in runs
                       if masked or not masked_only))

    visited = tiles(False)
    return (visited, tiles(True),
            (positions // bqp) * (positions // bk) - visited)


def _rule_subtiles(rule, positions, bqp, bk, cut_k):
    """(visited, masked) score SUB-tiles ([bqp, cut_k]) of ONE (batch, kv
    head) of a kernel that walks `rule` by `_walk_cut_runs`, from the runs it
    walks: a k block's worth a turn, but one for a cut k block with one
    sub-tile in sight."""
    ratio = bk // cut_k
    q_lo = np.arange(0, positions, bqp)  # every q tile at once
    fine = rule.key_runs(q_lo, bqp, cut_k, np)
    visited = masked = 0
    for lo, hi, cut in rule.key_runs(q_lo, bqp, bk, np):
        lo, hi = (np.broadcast_to(x, q_lo.shape) for x in (lo, hi))
        if not cut:
            visited += int(np.sum(np.maximum(hi - lo, 0))) * ratio
            continue
        for j in (lo + d for d in range(int(np.max(hi - lo, initial=0)))):
            lone = _seen_of_tile(j, fine, ratio, np)[0] == 1
            turns = int(np.sum(np.where(j < hi, np.where(lone, 1, ratio), 0)))
            visited += turns
            masked += turns
    return visited, masked


# --- the plan: resident or gridded, per kernel ----------------------------
#
# One algorithm with one parameter that follows from the call's shapes: is
# the sequence resident in VMEM? If it is, the innermost grid axis of the
# gridded kernels above becomes a loop inside the kernel (below); if not,
# the gridded kernels run as they always have.

# What the whole-sequence operands of one resident kernel may take in
# VMEM, both pipeline buffers counted. The v5e has 128 MiB of VMEM; the
# budget is set by what was swept, not by what would fit: the resident
# form beat the gridded one by 32-50% a kernel at every shape tried (D=128
# and 64, group 1 and 3, L=1024 to 8192; PERF.md, PR 28),
# the largest of them L=8192's dK/dV at D=128, 24 MiB.
RESIDENT_VMEM_BUDGET = 24 * 2 ** 20
# Mosaic's default scoped-VMEM limit on the v5e: a resident kernel asks
# for more through `vmem_limit_bytes` when its own sum says so.
_DEFAULT_VMEM_LIMIT = 16 * 2 ** 20

FlashKernelPlan = collections.namedtuple(
    "FlashKernelPlan",
    "path held block_q block_k grid grid_steps resident_bytes vmem_bytes "
    "vmem_limit_bytes tiles_visited tiles_masked tiles_skipped "
    "cut_k subtiles_visited subtiles_masked",
    defaults=(None,) * 6)
FlashKernelPlan.__doc__ = """How one flash kernel of a call runs.

path: "resident" (grid (B*G, blocks); the other sequence whole in VMEM,
walked by a loop in the kernel) or "gridded" (grid (B*G, blocks, blocks),
one pipeline step a tile). held: the side a grid step holds ONE block of
while it walks the other's: "q" (the forward, dQ; the grid's block axis
counts q blocks) or "k" (dK/dV; k blocks). The one-kernel backward has
two held sides: "k" (q, dO, lse, delta and dQ whole in VMEM with an f32
accumulator of dQ), and "q" where that does not fit: k, v, dk and dv whole
in VMEM, dK and dV summed over the q blocks in two f32 accumulators there,
dQ a block a step. block_q counts ROWS of the grouped layout.
grid_steps: pipeline steps the call issues. resident_bytes: the
whole-sequence operands, double-buffered, and a resident kernel's f32
accumulators, one buffer (0 when gridded). vmem_bytes:
what the call's block specs and scratch take as padded in VMEM, both
pipeline buffers counted. vmem_limit_bytes: what the call passes to
Mosaic, from its own sum — the buffers, the kernel's values (s, p, dp,
ds, the carried state) and a quarter more — never under the compiler's
default (None: the default itself, which every gridded block table
fits). tiles_visited / tiles_masked / tiles_skipped (with `mask=` only, else
None): the [block_q // group, block_k] score tiles of one call, over all
batch x kv heads, that the kernel computes, that it computes with the
rule's mask pass, and that it neither computes nor fetches: a k block the
kernel computes any part of counts as visited, one it masks any part of as
masked. cut_k / subtiles_visited / subtiles_masked (with `mask=` only): the
key step at which the kernel takes a cut k block with one sub-tile in sight
(`_cut_k`: under block_k in the one-kernel backward held by the q block; else
block_k itself: the kernel walks k blocks alone), and the [block_q // group,
cut_k] SUB-tiles it computes and masks, from the runs it walks: a k block's
worth a turn, one for such a turn. The area under the mask pass is
tiles_masked x block_k keys a query tile by k blocks alone and
subtiles_masked x cut_k as walked; cut_k ==
block_k reads subtiles_* == tiles_*."""


def _vmem(rows, cols, itemsize):
    """Bytes of a [rows, cols] array in VMEM: the lanes pad to 128."""
    return rows * -(-cols // 128) * 128 * itemsize


# Per kernel: arrays of width D in the inputs' dtype on the q side (q, o
# / dO, dq) and on the k side (k, v, dk, dv), and 8-wide f32 stripes on
# the q side (lse, delta).
_OPERANDS = {profile.FLASH_FWD: (2, 2, 1), profile.FLASH_DQ: (3, 2, 2),
             profile.FLASH_DKV: (2, 4, 2), profile.FLASH_BWD: (3, 4, 2)}
# Under a second score product, the arrays of ITS width on the q side (q2,
# dq2) and on the k side (k2, dk2).
_SHARED_OPERANDS = {profile.FLASH_FWD: (1, 1), profile.FLASH_DQ: (2, 1),
                    profile.FLASH_DKV: (1, 2), profile.FLASH_BWD: (2, 2)}


# The kernels that hold a k block and walk the q blocks (the others hold a
# q block and walk the k blocks).
_K_HELD = (profile.FLASH_DKV, profile.FLASH_BWD)
# The one-kernel backward's forms by the side a grid step holds a block of,
# in the order `flash_plan` tries them (tests and the block sweep narrow it
# to reach the second, or the two kernels, where the first fits).
_BWD_HELD = ("k", "q")
# The key step at which the one-kernel backward held by the q block walks a
# rule's cut runs, where it divides the k block (`_cut_k`; tests and the block
# sweep set it to reach a finer or a coarser walk: at the k block's own width
# the walk is by k blocks alone). Swept on the v5e: `_resident_blocks`.
_CUT_K = 256


def _resident_blocks(D, L, group, kernel):
    """Preferred (rows cap, block_k) inside the resident kernels' loop.
    The reason for wide blocks on the gridded path, amortising a grid
    step, is gone here; what is left is a loop turn's own overhead and
    the size of s [BQ, BK] in VMEM. v5e sweep (PR 28,
    examples/flash_block_sweep.py --path resident; ms a kernel and
    layer). Group 1, the same answer at D=128 (L=2048, 4096, 8192) and
    D=64 (L=1024, 2048): forward and dQ
    (512, 512), dK/dV (512, 1024) — at 2 x 16 x 2048 x 128: 0.442 /
    0.532 / 0.714 against 0.466 / 0.572 / 0.857 for the gridded table's
    (256, 512) on this path, and 0.998 / 0.977 / 1.283 gridded. Group 3
    at D=128 (L=2048): k blocks of 512 and as many rows as the gridded
    long-sequence cap, (1536, 512): 0.537 / 0.472 / 0.548 (384 x 512:
    0.514 / 0.497 / 0.646). Grouped layouts at D<=64 were not swept on
    this path and keep the gridded tables.

    The one-kernel backward (`hvd_flash_bwd`) keeps dK/dV's blocks: v5e
    sweep (PR 33, `--path resident` against `--path split`; ms a layer,
    the whole backward). 2 x 16 x 2048 x 128: dQ 0.523 + dK/dV 0.722 =
    1.246 as two, as one (512, 1024) 0.876, (512, 512) 0.869, (1024, 512)
    0.862, (1024, 1024) 0.889, (256, 1024) 0.891, (256, 512) 0.914,
    bk 256: 1.02-1.45. 1 x 16 x 4096 x 128: 1.961 as two; (512, 1024)
    1.387, (1024, 512) 1.378, (1024, 1024) 1.384, (256, 1024) 1.449,
    (512, 512) 1.456. D=64 at 2 x 16 x 2048: 1.246; (512, 1024) 0.883,
    (512, 512) 0.867. Group 3 (2 x 6 heads on 2, L=2048, D=128):
    0.605; (1536, 512) 0.358, (768, 512) 0.371, (1536, 1024) 0.408. The
    same pair read twice differs by up to 0.05, so nothing here beats the
    dK/dV kernel's table by more than the reading's own spread. Held by
    the q block it keeps that table too (PR 49; ms a call): group 8 under
    the block-diffusion rule (1 x 32 on 4 x 8192 x 128) (1024, 512) 6.34,
    (2048, 512) 6.47, (512, 512) 6.93, (1024, 1024) 7.18, as two 9.44;
    group 1 (1 x 16 x 8192 x 128) (512, 1024) 4.64, (512, 512) 5.12, as
    two 6.86.

    Under the causal band the forward and the q-held backward keep the
    same table (PR 52, `--window 1024 --path q-held`; group 8, 1 x 32 on 4
    x 8192 x 128; ms a call, forward / backward): the plan's (1024, 512)
    3.25 / 4.16; (512, 512) 3.13 / 4.41, (2048, 512) 3.31 / 4.30, (512, 256)
    3.62 / 4.57, (1024, 256) 3.42 / 4.15, (2048, 256) 3.34 / 4.02, (512,
    128) 4.74 / 6.87, (1024, 128) 4.18 / 6.05, (2048, 128) 4.29 / 6.22. A
    narrower k block wastes less of the two cut runs (1151 visible keys a
    query tile are 3-4 tiles of 512, 5-6 of 256, 10 of 128) and loses all
    the same from 128 down: a loop turn's own cost on a [1024, 128] x [128,
    bk] product; 256 and 512 tie to the reading's spread, and the table
    stays one.

    How the kernels held by the q block WALK a rule's runs at those blocks
    (PR 53; `--mask-block 4` / `--window 1024 --path q-held --cut-k`; the two
    cells' calls, 1 x 32 on 4 x 8192 x 128; ms a call, forward / backward,
    block diffusion then band). By k blocks, a loop a run, as PR 38-52:
    4.60 / 6.33 and 3.25 / 4.16. What was asked for first, every cut run a
    128-key sub-tile at a time and a whole run's remainder likewise: 4.98 /
    6.65 and 4.05 / 5.14 (256: 4.78 / 6.21 and 3.64 / 4.18): SLOWER with a
    quarter of the masked area and 15-25% less visited, because a turn's
    cost hardly follows its width. With parts of that walk left out (timing
    only): a 128-key turn is 1.75-1.9 us in a loop of several and 2.6-3.0 in
    a loop of its own where a whole 512-key turn is 2.2 (the state, 1.5 MiB
    of f32 with the running max and sum one lane wide, is read and written a
    turn whatever the tile); the mask pass on it 0.07-0.37 us; and EVERY LOOP
    ENTERED costs about 0.85 us in the forward (less in the backward, whose
    carry is dQ alone), which is most of what a cut tile seemed to cost over
    a whole one (4.0-5.3 us against 2.2: three loops a q tile, a tile or two
    each). So: fewer loops, and narrow turns only where they replace a wide
    one. The forward's state in VMEM scratch (the loops carry nothing): 5.95
    / - and 3.63 / - with 128-lane max and sum, 5.54 and 3.36 one lane wide:
    worse, it stays the carry, and the runs of a kind go in ONE loop
    (`_walk_runs_merged`): 4.41 and 3.00 (-4%, -8%). The backward's dQ in
    scratch: - / 6.11 and - / 4.02; and then, its loops free, a cut k block
    with one sub-tile in sight as that sub-tile (`_walk_cut_runs`): 128 keys
    - / 5.82 and - / 3.86, **256 keys - / 5.77 and - / 3.70** (-9%, -11%;
    512, no cut: 6.21 / 4.06); with dQ carried the same walk read 6.24 /
    4.31, and the merged loops 6.23 / 4.07. `_CUT_K` is 256."""
    if group == 1:
        return (512, 1024) if kernel in _K_HELD else (512, 512)
    if D > 64:
        return (1536, 512)
    return _grouped_blocks(D, L, group, kernel != profile.FLASH_FWD)


def _cut_k(plan, kernel):
    """The key step of a kernel's walk under a rule, from its plan: `_CUT_K`
    for the one-kernel backward held by the q block (k and v whole in VMEM: a
    key tile is a slice at any multiple of it; its sums in scratch: a loop
    costs nothing to enter) where the k block is a whole number of them, else
    the k block itself: the forward and dQ carry their state through the
    loops (`_walk_runs_merged`), held by the k block the cut would be in q
    rows (`_walk_q`), gridded a tile is a pipeline step. It divides what the
    rule's `tiled` and `check` asked of the k block, since the k block does."""
    bk = plan.block_k
    fine = (kernel == profile.FLASH_BWD and plan.path == "resident"
            and plan.held == "q" and _CUT_K < bk and bk % _CUT_K == 0)
    return _CUT_K if fine else bk


def _kernel_plan(BG, rows, L, D, group, isz, kernel, block_q, block_k,
                 vmem_budget, D2=0, rule=None, held=None):
    """``held``: the side a grid step holds a block of, "k" for the kernels
    of `_K_HELD` and "q" for the others unless given: the one-kernel
    backward has a form of either kind (`flash_plan` tries `_BWD_HELD`)."""
    backward = kernel != profile.FLASH_FWD
    k_held = (kernel in _K_HELD) if held is None else held == "k"
    held = "k" if k_held else "q"
    n_q, n_k, n_stripes = _OPERANDS[kernel]
    n_q2, n_k2 = _SHARED_OPERANDS[kernel]
    # The whole backward in one kernel, one buffer each: held by the k
    # block dQ's f32 accumulator (and the second product's dQ2's beside
    # it); held by the q block dK's and dV's, which the gridded dK/dV keeps
    # a k block of (dQ's block is then the loop's carry).
    fused = kernel == profile.FLASH_BWD
    if fused and k_held:
        accumulators = _vmem(rows, D, 4) + _vmem(rows, D2, 4)
    else:
        accumulators = 2 * _vmem(L, D, 4) + _vmem(L, D2, 4) if fused else 0
    # Held by the q block under a second product the whole-sequence operands
    # have ONE pipeline buffer each (`_pallas_backward`).
    copies = 1 if fused and not k_held and D2 else 2

    def q_side(n):  # one pipeline buffer of n rows of every q-side operand
        return (_vmem(n, D, n_q * isz) + n_stripes * _vmem(n, 8, 4)
                + _vmem(n, D2, n_q2 * isz))

    def k_side(n):
        return _vmem(n, D, n_k * isz) + _vmem(n, D2, n_k2 * isz)

    # Under a rule the blocks divide what IT says they must (`tiled`).
    tiled = L if rule is None else rule.tiled(L)

    def blocks(preferred):
        bq = block_q or _pick_rows_block(tiled, preferred[0], group)
        bk = block_k or _pick_block(tiled, preferred[1])
        _check_blocks(rows, L, bq, bk, group)
        if rule is not None:
            rule.check(L, bq // group, bk)
        return bq, bk

    whole = q_side(rows) if k_held else k_side(L)
    resident = copies * whole + accumulators
    if resident <= vmem_budget:
        bq, bk = blocks(_resident_blocks(D, L, group, kernel))
        bqp = bq // group
        # The loop's peel is static only where one block tiles the other
        # (every pair the tables give; a caller's own blocks may not).
        if bqp % bk == 0 or bk % bqp == 0:
            buffers = resident + 2 * (k_side(bk) if k_held else q_side(bq))
            # Beside the buffers the kernel's values live in VMEM too: s,
            # p, dp, ds, their low-precision copies, the carried state.
            # Mosaic's own need, by a compile for a described v5e, is
            # 0.3-0.8 of this sum; the room matters beyond the kernel:
            # XLA keeps arrays of its own in VMEM between operations, and
            # with a fifth less asked here it placed them worse by 0.14 ms
            # of copies a step in `lm1b4_1chip` (PERF.md, PR 28).
            values = (6 * bq * bk * 4 + 4 * _vmem(max(bq, bk), D, 4)
                      + 2 * _vmem(max(bq, bk), D2, 4))
            limit = -(-(buffers + values) * 5 // 4 // 2 ** 20) * 2 ** 20
            grid = (BG, L // bk if k_held else rows // bq)
            return FlashKernelPlan(
                "resident", held, bq, bk, grid, grid[0] * grid[1], resident,
                buffers, max(_DEFAULT_VMEM_LIMIT, limit))
    if fused:
        return None  # no gridded form: a grid carries dQ or dK/dV, not both
    dkv = kernel == profile.FLASH_DKV  # gridded, it holds a k block
    bq, bk = blocks(_grouped_blocks(D, L, group, backward))
    num_qb, num_kb = rows // bq, L // bk
    # acc / dq_acc (and m, l) per q block, or dk_acc + dv_acc per k block.
    scratch = ((2 * _vmem(bk, D, 4) + _vmem(bk, D2, 4) if dkv
                else _vmem(bq, D, 4) + (_vmem(bq, D2, 4) if backward else 0))
               + (0 if backward else 2 * _vmem(bq, 128, 4)))
    grid = (BG, num_kb, num_qb) if dkv else (BG, num_qb, num_kb)
    return FlashKernelPlan("gridded", "k" if dkv else "q", bq, bk, grid,
                           BG * num_qb * num_kb, 0,
                           2 * (q_side(bq) + k_side(bk)) + scratch, None)


def flash_plan(B, H, L, D, group=1, dtype=jnp.bfloat16, backward=False,
               block_q=None, block_k=None, vmem_budget=RESIDENT_VMEM_BUDGET,
               shared_dim=0, mask=None):
    """How `flash_attention` runs q [B, H, L, D] against H // group kv
    heads: {kernel name: FlashKernelPlan} for the forward kernel
    (`hvd_flash_fwd`) or, with ``backward``, the backward: ONE kernel
    (`hvd_flash_bwd`: s, p, dp and ds of a tile formed once for dQ, dK and
    dV) where its operands are resident, else two (`hvd_flash_dq`,
    `hvd_flash_dkv`), which form them twice. THE place where the path is
    chosen, from what a call can see and nothing else: a kernel is
    resident when its whole-sequence operands, double-buffered, fit
    ``vmem_budget`` (forward and dQ hold k + v; dK/dV holds q + dO and
    lse + delta, whose 8-wide f32 rows pad to 128 lanes; the one-kernel
    backward holds dK/dV's and dQ's output block, and one f32 accumulator
    of dQ's shape) and one of its blocks tiles the other; gridded
    otherwise. At D=128 in bf16 with one head a kv head the one-kernel
    backward holds 8 MiB at L=2048, 16 at 4096 and 32 at 8192.

    The one kernel has a SECOND held side, tried where the first does not
    fit (the order is `_BWD_HELD`'s): held by the q block (`held` "q", its
    grid's block axis counts q blocks and runs in order), with k, v, dk
    and dv whole in VMEM, double-buffered, and dK and dV summed over the q
    blocks in two f32 accumulators [L, D] there: 3 KiB a position at D <=
    128 in bf16 whatever the head group (24 MiB at L=8192), where the first
    holds 4 KiB a position and query head of the group (256 MiB with 8 at
    L=8192). Where neither fits the backward is two kernels, dK/dV held by
    the k block where THAT fits and gridded where not.

    ``shared_dim`` = D2 > 0: the scores are of two products, q [.., D] on k
    and q2 [.., D2] on ONE key k2 a position for all H heads
    (`flash_attention`'s ``q_shared``, ``k_shared``); v is D wide. The sums
    above then hold q2, k2 and their gradients too (D2 pads to 128 lanes),
    and the same order of forms is tried: at D=128, D2=64 in bf16 with one
    head a kv head the one-kernel backward held by the k block holds 22 MiB
    at L=4096 (16 without the second product) and 44 at 8192; held by the q
    block it holds k, v, k2, dk, dv, dk2 in ONE pipeline buffer each (a
    whole-sequence operand's block changes once a head, where a copy that
    is waited for costs microseconds; the shared key's does not change
    within a batch) and three f32 accumulators, of dK, dV and of dK2 (summed
    over a batch's heads there: the grid's first axis runs in order): 3 KiB
    a position, 24 MiB at L=8192, every call up to there. Past it the
    backward is two kernels, dQ resident on k + v + k2 (double-buffered: 24
    MiB at 16384) and dK/dV held by the k block, resident or gridded; the
    forward is resident up to 16384 and gridded beyond. Every form is a
    Pallas kernel: a call with a second product never takes the blockwise
    jnp path on a TPU.

    ``mask``: a rule in place of the causal triangle; L counts ALL
    positions of the call (`BlockDiffusionMask`: 2 x the rule's length;
    `BandMask`: the sequence). The same choice of path and kernels, with
    blocks that divide what the rule says they must (`tiled`: a half of the
    block-diffusion pair, the whole of a band's sequence), and every plan
    says how many score tiles its kernel visits,
    masks and skips. The forward and dQ take a rule in their resident form
    only, the one-kernel backward in both of its, dK/dV resident or gridded
    (at D=128 in bf16 with 8 heads a kv head and 8192 positions: the
    forward resident on k + v, 8 MiB; the backward one kernel held by the q
    block: k, v, dk, dv and the two accumulators, 24 MiB, q + dO of a kv
    head being 64). Where the forward or dQ would be gridded the result is
    ``{}``, no kernel at all (the one case left), and the call is the
    blockwise jnp form; a second score product is refused beside a rule.

    `_pallas_forward_lse` and `_pallas_backward` run what this returns,
    so it is also the counter that says which path a program took
    (docs/TRACING.md; `hvd.profile.flash_plan`)."""
    BG, rows = B * H // group, L * group
    isz = jnp.dtype(dtype).itemsize

    if mask is not None and shared_dim:
        raise ValueError("a mask by rule has one score product")

    def plan(kernel, held=None):
        p = _kernel_plan(BG, rows, L, D, group, isz, kernel, block_q,
                         block_k, vmem_budget, shared_dim, mask, held)
        if mask is None or p is None:
            return p
        bqp, cut_k = p.block_q // group, _cut_k(p, kernel)
        tiles = _rule_tiles(mask, p.held, L, bqp, p.block_k)
        subtiles = tiles[:2] if cut_k == p.block_k else _rule_subtiles(
            mask, L, bqp, p.block_k, cut_k)
        return p._replace(cut_k=cut_k, **dict(zip(
            ("tiles_visited", "tiles_masked", "tiles_skipped",
             "subtiles_visited", "subtiles_masked"),
            (BG * n for n in tiles + subtiles))))

    def resident_or_none(plans):
        if mask is not None and any(
                p.path != "resident" for name, p in plans.items()
                if name != profile.FLASH_DKV):
            return {}
        return plans

    if not backward:
        return resident_or_none({profile.FLASH_FWD: plan(profile.FLASH_FWD)})
    for held in _BWD_HELD:
        fused = plan(profile.FLASH_BWD, held)
        if fused is not None:
            return {profile.FLASH_BWD: fused}
    return resident_or_none({profile.FLASH_DQ: plan(profile.FLASH_DQ),
                             profile.FLASH_DKV: plan(profile.FLASH_DKV)})


def _compiler_params(plan, carries=False, heads_in_order=False):
    """``carries``: the resident grid's block axis carries state in
    scratch (the one-kernel backward's dQ, or its dK and dV where it is held
    by the q block), so its steps run in order. ``heads_in_order``: so does
    its first axis (the shared key's gradient, summed over a batch's heads
    in scratch)."""
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary" if heads_in_order else "parallel",
                             "arbitrary" if carries else "parallel")
        if plan.path == "resident"
        else ("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit_bytes)


def _q_walk_specs(plan, L, D, group, causal):
    """Block specs of a kernel that holds a q block and walks the k
    blocks (forward, dQ) under `plan`: (q-side index map, k/v spec).
    Resident: k/v whole, their block index constant across q blocks;
    gridded: per tile, the index clamped to the causal frontier
    (`_kv_index_map`)."""
    bq, bk = plan.block_q, plan.block_k
    if plan.path == "resident":
        return (lambda b, i: (b, i, 0),
                pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0)))
    return (lambda b, i, j: (b, i, 0),
            pl.BlockSpec((None, bk, D),
                         _kv_index_map(bq // group, bk, causal)))


# --- the resident kernels --------------------------------------------------

def _walk_k_blocks(visit, carry, qi, bqp, bk, num_kb, causal):
    """Runs ``visit(j, carry, masked)`` over the k blocks q block `qi`
    sees, ascending (the gridded kernels' order): the blocks wholly at
    or below the diagonal in a loop with no mask, then the one block
    (bqp <= bk) or bqp // bk blocks that straddle it, peeled, masked
    unconditionally. Not causal: one loop over all of them."""
    if not causal:
        return lax.fori_loop(0, num_kb, lambda j, c: visit(j, c, False),
                             carry)
    n_full = (qi * bqp) // bk
    carry = lax.fori_loop(0, n_full, lambda j, c: visit(j, c, False),
                          carry)
    for t in range(max(1, bqp // bk)):
        carry = visit(n_full + t, carry, True)
    return carry


def _walk_q_blocks(visit, carry, kj, bqp, bk, num_qb, causal):
    """The dK/dV twin of `_walk_k_blocks`: the q blocks k block `kj`
    is seen by, ascending from the first at or below the diagonal
    ((kj*bk) // bqp, as `_q_index_map` clamps to) — first the one
    (bk <= bqp) or bk // bqp that straddle it, peeled and masked, then
    the rest to the end in a loop."""
    if not causal:
        return lax.fori_loop(0, num_qb, lambda i, c: visit(i, c, False),
                             carry)
    first = (kj * bk) // bqp
    n_peel = max(1, bk // bqp)
    for t in range(n_peel):
        carry = visit(first + t, carry, True)
    return lax.fori_loop(first + n_peel, num_qb,
                         lambda i, c: visit(i, c, False), carry)


def _walk_k(visit, carry, qi, bqp, bk, num_kb, causal, rule):
    """The k blocks q block `qi` sees, for a kernel whose loops carry its
    state: the causal walk, or a rule's runs, those of a kind in one loop
    (`_walk_runs_merged`)."""
    if rule is None:
        return _walk_k_blocks(visit, carry, qi, bqp, bk, num_kb, causal)
    return _walk_runs_merged(visit, carry, rule.key_runs(qi * bqp, bqp, bk))


def _walk_q(visit, carry, kj, bqp, bk, num_qb, causal, rule):
    """The q blocks k block `kj` is seen by: likewise, a rule's runs at the
    q block's own step. A cut here would be in q ROWS (sub-tiles of the q
    block, `query_runs` at a finer step); no benchmark cell runs a kernel
    held by the k block under a rule (their backward is held by the q block),
    so this walk stays as it is."""
    if rule is None:
        return _walk_q_blocks(visit, carry, kj, bqp, bk, num_qb, causal)
    return _walk_runs(visit, carry,
                      rule.query_runs(kj * bk, bk, bqp, num_qb * bqp))


def _mask_tile(s, rule, q_off, kv_off, group):
    """The mask pass of a tile the walk marked: the rule's, or causal."""
    if rule is None:
        return _causal_mask(s, q_off, kv_off, -jnp.inf, group)
    return _rule_mask(s, rule, q_off, kv_off, -jnp.inf, group)


def _fwd_resident_kernel(*refs, scale, causal, bk, bqp, group,
                         shared=False, rule=None):
    # q_ref/o_ref: [BQ, D]; k_ref/v_ref: [L, D], fetched once per b (the
    # block index does not change across q blocks); lse_ref [BQ, 8]. The
    # online-softmax state (acc, m, l) is carried by the loop.
    # `shared`: q2_ref [BQ, D2] and k2_ref [L, D2] follow v (`_scores2`).
    # `rule`: a mask by rule in place of the causal triangle; the loop
    # walks the rule's runs of k blocks (`_walk_runs`).
    if shared:
        q2_ref, k2_ref = refs[3:5]
        refs = refs[:3] + refs[5:]
        q2 = q2_ref[...]
    q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    qi = pl.program_id(1)
    bq, D = q_ref.shape
    q = q_ref[...]

    def visit(j, carry, masked):
        acc, m_prev, l_prev = carry
        at = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[at, :]
        s = _scores2(q, k, q2, k2_ref[at, :], scale) if shared \
            else _scores(q, k, scale)
        if masked:
            s = _mask_tile(s, rule, qi * bqp, j * bk, group)
        # The first visited block covers every row (ascending order), so
        # no row's running max is still -inf after it.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_base = m_new
        if masked and rule is not None:
            # A rule's cut tile may hold no key of a row that later tiles
            # do: its max is then still -inf, and the row's terms must read
            # exp(-inf) = 0, not exp(-inf + inf).
            m_base = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_base)
        p = jnp.exp(s - m_base)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[at, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc, m, l = _walk_k(
        visit, (jnp.zeros((bq, D), jnp.float32),
                jnp.full((bq, 1), -jnp.inf, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32)),
        qi, bqp, bk, k_ref.shape[0] // bk, causal, rule)
    l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible keys
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _bwd_dq_resident_kernel(*refs, scale, causal, bk, bqp, group,
                            shared=False, rule=None):
    """dQ with k and v whole in VMEM: `_bwd_dq_kernel`'s arithmetic,
    the dq accumulator carried by the loop. `shared`: q2_ref [BQ, D2] and
    k2_ref [L, D2] follow v and dq2_ref [BQ, D2] is the last result; the
    loop carries (dq, dq2)."""
    if shared:
        *refs, dq2_ref = refs
        q2_ref, k2_ref = refs[3:5]
        refs = refs[:3] + refs[5:]
        q2 = q2_ref[...]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    qi = pl.program_id(1)
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[:, :1]
    delta = delta_ref[:, :1]

    def visit(j, dq, masked):
        if shared:
            dq, dq2 = dq
        at = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[at, :]
        s = _scores2(q, k, q2, k2_ref[at, :], scale) if shared \
            else _scores(q, k, scale)
        if masked:
            s = _mask_tile(s, rule, qi * bqp, j * bk, group)
        p = jnp.exp(s - lse)  # masked entries: exp(-inf) = 0
        dp = jax.lax.dot_general(
            do, v_ref[at, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq = dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared:
            return dq, dq2 + jax.lax.dot_general(
                ds.astype(k.dtype), k2_ref[at, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return dq

    zeros = jnp.zeros(q.shape, jnp.float32)
    dq = _walk_k(
        visit, (zeros, jnp.zeros(q2.shape, jnp.float32)) if shared
        else zeros, qi, bqp, bk, k_ref.shape[0] // bk, causal, rule)
    if shared:
        dq, dq2 = dq
        dq2_ref[...] = dq2.astype(dq2_ref.dtype)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_resident_kernel(*refs, scale, causal, bq, bqp, group,
                             with_dq, shared=False, rule=None):
    """dK/dV with q, dO, lse and delta whole in VMEM: `_bwd_dkv_kernel`'s
    arithmetic, the dk and dv accumulators carried by the loop.
    ``with_dq`` (`hvd_flash_bwd`): the whole backward in this one kernel.
    s, p, dp and ds of a tile are formed once and serve dQ too: every
    visit adds ds.k to the rows of its q block in an f32 [rows, D]
    accumulator that lives in VMEM scratch across the grid's k-block axis,
    zeroed at the first k block of a (batch, kv head) and written to the
    dQ output, cast once, at the last. dQ's sum over k blocks runs in
    ascending k order in f32, as `_bwd_dq_resident_kernel`'s loop runs it.

    ``shared``: the scores are of two products (`_scores2`). q2_ref [rows,
    D2] whole and k2_ref [BK, D2] follow v; dk2_ref [BK, D2], THIS head's
    part of the shared key's gradient, follows dv, and with ``with_dq``
    dq2_ref [rows, D2] follows dq and its f32 accumulator dq's."""
    if with_dq and shared:
        *refs, dq_ref, dq2_ref, dq_acc, dq2_acc = refs
    elif with_dq:  # the third result and the one scratch
        *refs, dq_ref, dq_acc = refs
    if shared:
        *refs, dk2_ref = refs
        q2_ref, k2_ref = refs[3:5]
        refs = refs[:3] + refs[5:]
        k2 = k2_ref[...]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref = refs
    kj = pl.program_id(1)
    bk = k_ref.shape[0]
    k = k_ref[...]
    v = v_ref[...]

    if with_dq:
        @pl.when(kj == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            if shared:
                dq2_acc[...] = jnp.zeros_like(dq2_acc)

    def visit(i, carry, masked):
        dk, dv, *dk2 = carry
        at = pl.ds(pl.multiple_of(i * bq, bq), bq)
        q = q_ref[at, :]
        do = do_ref[at, :]
        if shared:
            q2 = q2_ref[at, :]
            s = _scores2(q, k, q2, k2, scale)
        else:
            s = _scores(q, k, scale)
        if masked:
            s = _mask_tile(s, rule, i * bqp, kj * bk, group)
        p = jnp.exp(s - lse_ref[at, :1])  # masked entries: exp(-inf) = 0
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[at, :1]) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            dq_acc[at, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if shared:
            if with_dq:
                dq2_acc[at, :] += jax.lax.dot_general(
                    ds, k2, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return dk, dv, dk2[0] + jax.lax.dot_general(
                ds, q2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros(k.shape, jnp.float32)
    dk, dv, *dk2 = _walk_q(
        visit, (zeros, zeros) + (
            (jnp.zeros(k2.shape, jnp.float32),) if shared else ()),
        kj, bqp, bk, q_ref.shape[0] // bq, causal, rule)
    if shared:
        dk2_ref[...] = dk2[0].astype(dk2_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    if with_dq:
        @pl.when(kj == pl.num_programs(1) - 1)
        def _finalize():
            def store(i, carry):  # a q block at a time: bounded values
                at = pl.ds(pl.multiple_of(i * bq, bq), bq)
                dq_ref[at, :] = dq_acc[at, :].astype(dq_ref.dtype)
                if shared:
                    dq2_ref[at, :] = dq2_acc[at, :].astype(dq2_ref.dtype)
                return carry

            lax.fori_loop(0, q_ref.shape[0] // bq, store, 0)


def _bwd_q_held_kernel(*refs, scale, causal, bk, bqp, group, rule=None,
                       cut_k=None, shared=False, heads=1):
    """The whole backward (`hvd_flash_bwd`) held by the q block, with k and
    v whole in VMEM: on `_bwd_dq_resident_kernel`'s grid and walk, s, p, dp
    and ds of a tile formed once for dQ, dK and dV. A step takes a q block
    (q, dO, lse, delta) and walks the k blocks it sees. dQ of the block is
    the loop's carry, as that kernel's: summed in ascending k order in f32
    and complete within the step. Each tile's p^T.dO and ds^T.q are added
    to the rows of its k block in two f32 [L, D] accumulators in VMEM
    scratch across the grid's q-block axis: zeroed at the first q block of
    a (batch, kv head), rounded once and written, whole blocks too, at the
    last. A k block's sum over the q blocks, the head group's rows among
    them, runs in ascending q order, as the gridded `_bwd_dkv_kernel`'s.

    `rule`: dQ of the block is summed in a third accumulator in scratch
    (`dq_acc` [BQ, D] f32) in place of the carry, so that the walk's loops
    carry nothing and cost nothing to enter, and the walk is
    `_walk_cut_runs` at the key step `cut_k`: a cut k block with one
    sub-tile in sight is a turn of that sub-tile, its p^T.dO and ds^T.q
    added at the sub-tile's rows.

    `shared`: the scores are of two products (`_scores2`). q2_ref [BQ, D2]
    and k2_ref [L, D2], the ONE key of the batch's `heads` kv heads, follow
    v; dk2_ref [L, D2] follows dv, dq2_ref [BQ, D2] follows dq, and a third
    f32 accumulator dk2_acc [L, D2] the other two. dQ2 of the block is the
    loop's carry beside dQ. The shared key's gradient is summed where it is
    formed: dk2_acc lives across ALL the heads of a batch (zeroed at its
    first head's first q block, rounded once and written at its last head's
    last), so that no head's part of it ever reaches HBM; the grid's first
    axis then runs in order too."""
    if shared:
        (q_ref, k_ref, v_ref, q2_ref, k2_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk2_ref, dq_ref, dq2_ref, dk_acc, dv_acc,
         dk2_acc) = refs
        q2 = q2_ref[...]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dq_ref, dk_acc, dv_acc, *dq_acc) = refs
    qi = pl.program_id(1)
    num_kb = k_ref.shape[0] // bk

    def k_block(j):
        return pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(qi == 0)
    def _init():
        zeros = jnp.zeros((bk, dk_acc.shape[1]), jnp.float32)

        def zero(j, carry):  # a k block at a time: bounded values
            dk_acc[k_block(j), :] = zeros
            dv_acc[k_block(j), :] = zeros
            return carry

        lax.fori_loop(0, num_kb, zero, 0)

    if shared:
        head = pl.program_id(0) % heads

        @pl.when((qi == 0) & (head == 0))
        def _init_shared():
            zeros = jnp.zeros((bk, dk2_acc.shape[1]), jnp.float32)

            def zero(j, carry):
                dk2_acc[k_block(j), :] = zeros
                return carry

            lax.fori_loop(0, num_kb, zero, 0)

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[:, :1]
    delta = delta_ref[:, :1]

    def tile(j, step, width, masked):
        """ds.k of the `width` keys from tile `j` of `step` keys on (and
        ds.k2 beside it under `shared`), their p^T.dO and ds^T.q (and
        ds^T.q2) added to dV's and dK's (and dK2's) rows."""
        at = pl.ds(pl.multiple_of(j * step, step), width)
        k = k_ref[at, :]
        s = _scores2(q, k, q2, k2_ref[at, :], scale) if shared \
            else _scores(q, k, scale)
        if masked:
            s = _mask_tile(s, rule, qi * bqp, j * step, group)
        p = jnp.exp(s - lse)  # masked entries: exp(-inf) = 0
        dv_acc[at, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[at, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[at, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if not shared:
            return dq
        dk2_acc[at, :] += jax.lax.dot_general(
            ds, q2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dq, jax.lax.dot_general(
            ds, k2_ref[at, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if shared:
        def visit2(j, carry, masked):
            dq, dq2 = tile(j, bk, bk, masked)
            return carry[0] + dq, carry[1] + dq2

        dq, dq2 = _walk_k(
            visit2, (jnp.zeros(q.shape, jnp.float32),
                     jnp.zeros(q2.shape, jnp.float32)),
            qi, bqp, bk, num_kb, causal, rule)
        dq2_ref[...] = dq2.astype(dq2_ref.dtype)
    elif rule is None:
        dq = _walk_k(
            lambda j, dq, masked: dq + tile(j, bk, bk, masked),
            jnp.zeros(q.shape, jnp.float32), qi, bqp, bk, num_kb, causal,
            rule)
    else:
        dq_acc, = dq_acc
        dq_acc[...] = jnp.zeros(q.shape, jnp.float32)

        def visit(j, masked, width):
            dq_acc[...] += tile(j, cut_k, width, masked)

        _walk_cut_runs(visit, rule.key_runs(qi * bqp, bqp, bk),
                       rule.key_runs(qi * bqp, bqp, cut_k), bk, cut_k)
        dq = dq_acc[...]
    dq_ref[...] = dq.astype(dq_ref.dtype)

    @pl.when(qi == pl.num_programs(1) - 1)
    def _finalize():
        def store(j, carry):
            at = k_block(j)
            dk_ref[at, :] = dk_acc[at, :].astype(dk_ref.dtype)
            dv_ref[at, :] = dv_acc[at, :].astype(dv_ref.dtype)
            return carry

        lax.fori_loop(0, num_kb, store, 0)

    if shared:
        @pl.when((qi == pl.num_programs(1) - 1) & (head == heads - 1))
        def _finalize_shared():
            def store(j, carry):
                at = k_block(j)
                dk2_ref[at, :] = dk2_acc[at, :].astype(dk2_ref.dtype)
                return carry

            lax.fori_loop(0, num_kb, store, 0)


def _shared_operands(shared, B, G, group):
    """(q2 in the grouped-rows layout, k2 [B, L, D2], the index of k2's
    batch from a kernel's first grid index) of a call with a second score
    product."""
    q2, k2 = shared
    return (_to_rows(q2, group), k2.reshape(B, k2.shape[2], k2.shape[3]),
            lambda b: b // G)


def _q_walk_shared_specs(plan, L, D2, group, causal, of_batch):
    """Beside `_q_walk_specs`, the second product's (q2 spec, k2 spec): q2
    by the q block; the shared key by its BATCH, whole where the kernel is
    resident (the block index is the same for every head of a batch, so it
    is fetched once a batch), else the k block the step's `_kv_index_map`
    names."""
    bq, bk = plan.block_q, plan.block_k
    if plan.path == "resident":
        return (pl.BlockSpec((None, bq, D2), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, L, D2),
                             lambda b, i: (of_batch(b), 0, 0)))
    kv_im = _kv_index_map(bq // group, bk, causal)
    return (pl.BlockSpec((None, bq, D2), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, D2), lambda b, i, j: (
                of_batch(b),) + kv_im(b, i, j)[1:]))


_RULED_CALLS = {}  # {what decides a ruled kernel's call: its jitted call}


def _ruled_call(call, name, rule, plan, inputs, *static):
    """`call` (a `pl.pallas_call`) as it is where `rule` is None (the
    accepted cells' program text is pinned); under a rule JITTED, one
    function for all calls that `static`, the plan and the operands'
    shapes make alike, as `ops/moe_rows.py`'s are: a model's blocks share
    one trace and one lowering of the kernel (a quarter of a second of a
    step's lowering each time), and the call site's scope path still
    reaches each call's `op_name`. Only the kernel's own call is inside:
    the change of layout around it stays where XLA fuses it with the
    caller's transposes (jitted with it, a layer kept 72 MiB more)."""
    if rule is None:
        return call
    key = (name, rule, plan, static,
           tuple((x.shape, x.dtype.name) for x in inputs))
    if key not in _RULED_CALLS:
        def ruled(*operands):
            return call(*operands)
        ruled.__name__ = ruled.__qualname__ = "_ruled_" + name
        _RULED_CALLS[key] = jax.jit(ruled)
    return _RULED_CALLS[key]


def _pallas_forward_lse(q, k, v, scale, causal, interpret,
                        block_q=None, block_k=None,
                        vmem_budget=RESIDENT_VMEM_BUDGET, shared=None,
                        rule=None):
    """q [B, H, L, D], k/v [B, G, L, D] with G | H. Returns
    (out [B,H,L,D], lse [B*G, L*group, 8] f32) — lse is the per-row
    log-sum-exp the backward kernels need, in the grouped-rows layout
    (replicated over an 8-wide trailing dim: keeps the block
    Mosaic-tileable and the DMA a contiguous stripe; 1-wide measured
    slower, 128-wide wastes 16x the memory). `flash_plan` chooses the
    path and the blocks; ``vmem_budget`` is its argument (tests and the
    block sweep force a path with it). ``shared``: (q2 [B, H, L, D2], k2
    [B, 1, L, D2]), the second score product's operands. ``rule``: a mask
    by rule in place of ``causal`` (resident only: `flash_plan`)."""
    B, H, L, D = q.shape
    G = k.shape[1]
    group = H // G
    qf = _to_rows(q, group)
    kf = k.reshape(B * G, L, D)
    vf = v.reshape(B * G, L, D)
    D2 = shared[0].shape[-1] if shared else 0
    plans = flash_plan(B, H, L, D, group, q.dtype, False, block_q, block_k,
                       vmem_budget, D2, rule)
    if shared:
        q2f, k2f, of_batch = _shared_operands(shared, B, G, group)
    if rule is not None and not plans:
        raise NotImplementedError(
            "a mask by rule exists in the resident forward kernel only; "
            "`flash_plan(..., mask=%r)` says this call's is not" % (rule,))
    plan = plans[profile.FLASH_FWD]
    bq, bk = plan.block_q, plan.block_k
    rows = L * group
    bqp = bq // group
    inputs = [qf, kf, vf] + ([q2f, k2f] if shared else [])
    q_im, kv_spec = _q_walk_specs(plan, L, D, group, causal)
    if plan.path == "resident":
        kernel = functools.partial(_fwd_resident_kernel, scale=scale,
                                   causal=causal, bk=bk, bqp=bqp,
                                   group=group,
                                   **({"shared": True} if shared else {}),
                                   **({} if rule is None
                                      else {"rule": rule}))
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   num_kb=L // bk, bqp=bqp, group=group,
                                   **({"shared": True} if shared else {}))
        scratch = [
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ]
    q_spec = pl.BlockSpec((None, bq, D), q_im)
    shared_specs = list(_q_walk_shared_specs(
        plan, L, D2, group, causal, of_batch)) if shared else []
    out, lse = _ruled_call(pl.pallas_call(
        kernel,
        name=profile.FLASH_FWD,
        grid=plan.grid,
        in_specs=[q_spec, kv_spec, kv_spec] + shared_specs,
        out_specs=[q_spec, pl.BlockSpec((None, bq, 8), q_im)],
        out_shape=[
            jax.ShapeDtypeStruct((B * G, rows, D), q.dtype),
            jax.ShapeDtypeStruct((B * G, rows, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(plan),
        interpret=interpret,
    ), profile.FLASH_FWD, rule, plan, inputs, scale, interpret)(*inputs)
    return _from_rows(out, B, group), lse


def _pallas_forward(q, k, v, scale, causal, interpret,
                    block_q=None, block_k=None):
    return _pallas_forward_lse(q, k, v, scale, causal, interpret,
                               block_q, block_k)[0]


def _ring_step_kernel(q_offs_ref, kv_offs_ref, q_ref, k_ref, v_ref, oi_ref,
                      mi_ref, li_ref, oo_ref, mo_ref, lo_ref, acc_ref, m_ref,
                      l_ref, *, scale, causal, num_kb, bqp, group):
    """One ring-attention step as a flash kernel with carried state.

    Same online-softmax update as `_fwd_kernel`, but the (acc, m, l)
    state is loaded from the previous ring step's outputs instead of
    initialized, and written back un-normalized (the caller divides by l
    after the last ring step). Causal masking uses *global* token
    offsets — PER-BLOCK arrays in SMEM (q_offs_ref[qi], kv_offs_ref[kj],
    position units) rather than one scalar per shard, so a shard may
    hold discontiguous sequence chunks (the zigzag causal schedule) as
    long as chunk boundaries align with block boundaries. Block skipping
    is dynamic for the same reason.
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_off = q_offs_ref[qi]
    kv_off = kv_offs_ref[kj]

    @pl.when(kj == 0)
    def _load_state():
        acc_ref[...] = oi_ref[...]
        m_ref[...] = jnp.broadcast_to(mi_ref[:, :1], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(li_ref[:, :1], l_ref.shape)

    # A k/v block entirely in this q block's future contributes nothing.
    visible = (kv_off <= q_off + bqp - 1) if causal else kj >= 0

    @pl.when(visible)
    def _compute():
        s = _masked_scores(q_ref[...], k_ref[...], scale, causal,
                           q_off=q_off, kv_off=kv_off, fill=-jnp.inf,
                           group=group)
        _online_softmax_update(s, v_ref, acc_ref, m_ref, l_ref,
                               guard_empty=True)

    @pl.when(kj == num_kb - 1)
    def _store_state():
        oo_ref[...] = acc_ref[...]
        mo_ref[...] = jnp.broadcast_to(m_ref[:, :1], mo_ref.shape)
        lo_ref[...] = jnp.broadcast_to(l_ref[:, :1], lo_ref.shape)


def _chunk_len(L, offset, what):
    """Chunk length for a scalar shard offset (one chunk = the shard)
    or a 1-D array of per-chunk offsets (equal chunks)."""
    arr = jnp.asarray(offset)
    if arr.ndim == 0:
        return L
    if L % arr.shape[0]:
        raise ValueError(f"{what}: {arr.shape[0]} chunks must divide "
                         f"shard length {L}")
    return L // arr.shape[0]


def _block_offsets(offset, L, blk):
    """Per-block global offsets (L // blk,) int32 from a scalar shard
    offset or a 1-D array of per-chunk offsets (equal chunks whose
    length must be a multiple of blk — blocks may not straddle chunk
    boundaries). Position units throughout."""
    off = jnp.asarray(offset, jnp.int32)
    pos = jnp.arange(L // blk, dtype=jnp.int32) * blk
    if off.ndim == 0:
        return off + pos
    Lc = L // off.shape[0]
    if Lc % blk:
        # Reachable only via an explicit block_q/block_k override that
        # bypasses the _require_rows_block(chunk_len, ...) pick: a block
        # spanning two discontiguous chunks would get one (wrong)
        # offset and silently mis-mask.
        raise ValueError(
            f"block size {blk} must divide the chunk length {Lc} "
            f"(chunked shards cannot have blocks straddling chunk "
            f"boundaries)")
    return off[pos // Lc] + pos % Lc


def flash_ring_step(q, k, v, o, m, l, q_offset, kv_offset, causal=True,
                    scale=None, interpret=False, block_q=None,
                    block_k=None, group=1):
    """One ring-attention local step over kernel-layout shards.

    Args: q [BG, Lq*group, D] grouped-rows layout (bf16/f32; group=1 is
    the plain [B*H, Lq, D] layout), k/v [BG, Lk, D], carried state
    o [BG, Lq*group, D] f32 (un-normalized accumulator), m/l
    [BG, Lq*group, 8] f32 (running max / normalizer stripes),
    q_offset/kv_offset global token POSITION offsets — traced int32
    scalars (contiguous shards), or 1-D arrays of per-chunk offsets for
    shards holding several equal discontiguous chunks (the zigzag
    causal schedule). Returns updated (o, m, l)."""
    BG, rows, D = q.shape
    Lq = rows // group
    Lk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    Lcq = _chunk_len(Lq, q_offset, "q_offset")
    Lck = _chunk_len(Lk, kv_offset, "kv_offset")
    pq, pk = _grouped_blocks(D, Lq, group)
    bq = block_q or _require_rows_block(Lcq, pq, group, "q chunk length")
    bk = block_k or _require_rows_block(Lck, pk, 1, "k/v chunk length")
    _check_blocks(rows, Lk, bq, bk, group)
    bqp = bq // group
    num_kb = Lk // bk
    q_offs = _block_offsets(q_offset, Lq, bqp)
    kv_offs = _block_offsets(kv_offset, Lk, bk)
    kernel = functools.partial(_ring_step_kernel, scale=scale,
                               causal=causal, num_kb=num_kb, bqp=bqp,
                               group=group)
    grid = (BG, rows // bq, num_kb)
    q_spec = pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0))
    state_specs = [
        q_spec,
        pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
    ]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # per-q-block offs
        pl.BlockSpec(memory_space=pltpu.SMEM),  # per-kv-block offs
        q_spec, kv_spec, kv_spec,
    ]
    return pl.pallas_call(
        kernel,
        name=profile.RING_ATTN,
        grid=grid,
        in_specs=in_specs + state_specs,
        out_specs=state_specs,
        out_shape=[
            jax.ShapeDtypeStruct((BG, rows, D), jnp.float32),
            jax.ShapeDtypeStruct((BG, rows, 8), jnp.float32),
            jax.ShapeDtypeStruct((BG, rows, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offs, kv_offs, q, k, v, o, m, l)


def _ring_bwd_dq_kernel(q_offs_ref, kv_offs_ref, q_ref, k_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dqi_ref, dqo_ref, dq_acc, *,
                        scale, causal, num_kb, bqp, group):
    """dQ contribution of one backward ring step (FlashAttention-2
    math, global offsets like `_ring_step_kernel`). The dq accumulator
    is carried *across ring steps* (dqi -> dqo, f32): each arriving k/v
    shard adds its `sum_k dS.K` term; no forward recompute — p comes
    from the saved per-row lse."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_off = q_offs_ref[qi]
    kv_off = kv_offs_ref[kj]

    @pl.when(kj == 0)
    def _load():
        dq_acc[...] = dqi_ref[...]

    visible = (kv_off <= q_off + bqp - 1) if causal else kj >= 0

    @pl.when(visible)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        s = _masked_scores(q, k, scale, causal, q_off=q_off,
                           kv_off=kv_off, fill=-jnp.inf, group=group)
        p = jnp.exp(s - lse_ref[:, :1])  # masked entries: exp(-inf) = 0
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, :1]) * scale)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_kb - 1)
    def _store():
        dqo_ref[...] = dq_acc[...]


def _ring_bwd_dkv_kernel(q_offs_ref, kv_offs_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, dki_ref, dvi_ref,
                         dko_ref, dvo_ref, dk_acc, dv_acc, *, scale, causal,
                         num_qb, bqp, group):
    """dK/dV contribution of one backward ring step. The dk/dv
    accumulators travel around the ring with their k/v shard (the
    caller ppermutes them together), so after n steps each shard
    arrives home with its full gradient. Grid (bg, k-block, q-block), q
    innermost sequential."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    q_off = q_offs_ref[qi]
    kv_off = kv_offs_ref[kj]

    @pl.when(qi == 0)
    def _load():
        dk_acc[...] = dki_ref[...]
        dv_acc[...] = dvi_ref[...]

    visible = (q_off + bqp - 1 >= kv_off) if causal else qi >= 0

    @pl.when(visible)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        s = _masked_scores(q, k, scale, causal, q_off=q_off,
                           kv_off=kv_off, fill=-jnp.inf, group=group)
        p = jnp.exp(s - lse_ref[:, :1])  # masked entries: exp(-inf) = 0
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, :1]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _store():
        dko_ref[...] = dk_acc[...]
        dvo_ref[...] = dv_acc[...]


def flash_ring_bwd_step(q, k, v, do, lse, delta, dq, dk, dv, q_offset,
                        kv_offset, causal=True, scale=None,
                        interpret=False, block_q=None, block_k=None,
                        group=1):
    """One backward ring step over kernel-layout shards.

    Args: q/do [BG, Lq*group, D] grouped-rows layout, k/v [BG, Lk, D],
    lse/delta [BG, Lq*group, 8] f32 (per-row log-sum-exp from the
    forward; delta = rowsum(dO*O)), dq [BG, Lq*group, D] f32 (local
    accumulator), dk/dv [BG, Lk, D] f32 (accumulators traveling with
    the k/v shard), q_offset/kv_offset global token position offsets.
    Returns updated (dq, dk, dv)."""
    BG, rows, D = q.shape
    Lq = rows // group
    Lk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    Lcq = _chunk_len(Lq, q_offset, "q_offset")
    Lck = _chunk_len(Lk, kv_offset, "kv_offset")
    pq, pk = _grouped_blocks(D, Lq, group, backward=True)
    bq = block_q or _require_rows_block(Lcq, pq, group, "q chunk length")
    bk = block_k or _require_rows_block(Lck, pk, 1, "k/v chunk length")
    _check_blocks(rows, Lk, bq, bk, group)
    bqp = bq // group
    num_kb, num_qb = Lk // bk, rows // bq
    q_offs = _block_offsets(q_offset, Lq, bqp)
    kv_offs = _block_offsets(kv_offset, Lk, bk)

    q_spec = lambda b, i, j: (b, i, 0)      # noqa: E731
    stripe_spec = lambda b, i, j: (b, i, 0)  # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_ring_bwd_dq_kernel, scale=scale, causal=causal,
                          num_kb=num_kb, bqp=bqp, group=group),
        name=profile.RING_ATTN_DQ,
        grid=(BG, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, bq, D), q_spec),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bq, D), q_spec),
            pl.BlockSpec((None, bq, 8), stripe_spec),
            pl.BlockSpec((None, bq, 8), stripe_spec),
            pl.BlockSpec((None, bq, D), q_spec),
        ],
        out_specs=pl.BlockSpec((None, bq, D), q_spec),
        out_shape=jax.ShapeDtypeStruct((BG, rows, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offs, kv_offs, q, k, v, do, lse, delta, dq)

    k_spec = lambda b, j, i: (b, j, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_ring_bwd_dkv_kernel, scale=scale,
                          causal=causal, num_qb=num_qb, bqp=bqp,
                          group=group),
        name=profile.RING_ATTN_DKV,
        grid=(BG, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, bk, D), k_spec),
            pl.BlockSpec((None, bk, D), k_spec),
            pl.BlockSpec((None, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((None, bk, D), k_spec),
            pl.BlockSpec((None, bk, D), k_spec),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), k_spec),
            pl.BlockSpec((None, bk, D), k_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BG, Lk, D), jnp.float32),
            jax.ShapeDtypeStruct((BG, Lk, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offs, kv_offs, q, k, v, do, lse, delta, dk, dv)
    return dq, dk, dv


def _bwd_dq_kernel(*refs, scale, causal, num_kb, bqp, group, shared=False):
    """dQ: grid (bg, q-block, k-block), k innermost sequential.
    Recomputes p = exp(s - lse) per block; dS = p * (dO.V^T - delta);
    dQ = sum_k dS.K * scale accumulated in VMEM scratch. lse and
    delta = rowsum(dO*O) are precomputed per row and streamed in.
    ``shared``: q2_ref [BQ, D2] and k2_ref [BK, D2] follow v, dq2_ref [BQ,
    D2] follows dq and its f32 accumulator dq's."""
    if shared:
        (q_ref, k_ref, v_ref, q2_ref, k2_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq2_ref, dq_acc, dq2_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         dq_acc) = refs
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_k = k_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if shared:
            dq2_acc[...] = jnp.zeros_like(dq2_acc)

    visible = (kj * block_k < (qi + 1) * bqp) if causal else kj >= 0

    @pl.when(visible)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        s = _masked_scores(q, k, scale, causal,
                           q_off=qi * bqp, kv_off=kj * block_k,
                           fill=-jnp.inf, group=group,
                           **({"shared": (q2_ref[...], k2_ref[...])}
                              if shared else {}))
        p = jnp.exp(s - lse_ref[:, :1])  # masked entries: exp(-inf) = 0
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared:
            dq2_acc[...] += jax.lax.dot_general(
                ds.astype(k.dtype), k2_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(kj == num_kb - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        if shared:
            dq2_ref[...] = dq2_acc[...].astype(dq2_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, num_qb, bqp, group, rule=None,
                    shared=False):
    """dK/dV: grid (bg, k-block, q-block), q innermost sequential.
    dV = sum_q P^T.dO; dK = sum_q dS^T.Q * scale. In the grouped GQA
    layout the q rows interleave the whole head group, so the group
    reduction of dK/dV happens in these same accumulators. ``shared``:
    q2_ref [BQ, D2] and k2_ref [BK, D2] follow v, dk2_ref [BK, D2] (THIS
    head's part of the shared key's gradient) follows dv and its f32
    accumulator dv's."""
    if shared:
        (q_ref, k_ref, v_ref, q2_ref, k2_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk2_ref, dk_acc, dv_acc, dk2_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    block_k = k_ref.shape[0]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if shared:
            dk2_acc[...] = jnp.zeros_like(dk2_acc)

    # Causal: q blocks entirely above this k block see none of it. A rule:
    # the q blocks of its runs for this k block (`_rule_q_index_map`
    # fetches no others), the mask pass on the runs that ask for it.
    if rule is not None:
        runs = rule.query_runs(kj * block_k, block_k, bqp, num_qb * bqp)
        visible = _in_runs(qi, runs)
    else:
        visible = (qi * bqp + (bqp - 1) >= kj * block_k) if causal \
            else qi >= 0

    @pl.when(visible)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        if rule is not None:
            s = jax.lax.cond(
                _in_runs(qi, runs, masked_only=True),
                lambda s: _rule_mask(s, rule, qi * bqp, kj * block_k,
                                     -jnp.inf, group),
                lambda s: s, _scores(q, k, scale))
        else:
            s = _masked_scores(q, k, scale, causal,
                               q_off=qi * bqp, kv_off=kj * block_k,
                               fill=-jnp.inf, group=group,
                               **({"shared": (q2_ref[...], k2_ref[...])}
                                  if shared else {}))
        p = jnp.exp(s - lse_ref[:, :1])  # masked entries: exp(-inf) = 0
        p_lo = p.astype(do_ref.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p_lo, do_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, :1]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared:
            dk2_acc[...] += jax.lax.dot_general(
                ds, q2_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if shared:
            dk2_ref[...] = dk2_acc[...].astype(dk2_ref.dtype)


def _pallas_backward(q, k, v, out, lse, g, scale, causal, interpret,
                     block_q=None, block_k=None,
                     vmem_budget=RESIDENT_VMEM_BUDGET, shared=None,
                     rule=None):
    """Pallas backward: q/out/g [B,H,L,D], k/v [B,G,L,D], lse in the
    grouped-rows layout. Returns (dq [B,H,L,D], dk/dv [B,G,L,D]) in the
    inputs' dtypes. Path and blocks per kernel from `flash_plan`. With
    ``shared`` = (q2 [B,H,L,D2], k2 [B,1,L,D2]) also (dq2, dk2) of those
    shapes, dk2 summed over the heads in f32. ``rule``: a mask by rule in
    place of ``causal`` (dQ resident only, the one kernel in either of its
    forms, dK/dV resident or gridded)."""
    B, H, L, D = q.shape
    G = k.shape[1]
    group = H // G
    qf, gf, outf = (_to_rows(x, group) for x in (q, g, out))
    kf = k.reshape(B * G, L, D)
    vf = v.reshape(B * G, L, D)
    # delta = rowsum(dO * O): one fused XLA pass, streamed into both
    # kernels per q block (recomputing it per grid step would redo the
    # reduction num_kb/num_qb times).
    delta = jnp.broadcast_to(
        jnp.sum(gf.astype(jnp.float32) * outf.astype(jnp.float32),
                axis=-1, keepdims=True), lse.shape)
    rows = L * group
    # Backward blocks are independent of the forward's (lse/delta
    # stripes are block-agnostic); see _resident_blocks and
    # _default_blocks for the swept preferences.
    D2 = shared[0].shape[-1] if shared else 0
    plans = flash_plan(B, H, L, D, group, q.dtype, True, block_q, block_k,
                       vmem_budget, D2, rule)
    if shared:
        q2f, k2f, of_batch = _shared_operands(shared, B, G, group)
        extra = {"shared": True}
        dq2_shape = jax.ShapeDtypeStruct((B * G, rows, D2), q2f.dtype)
    else:
        extra = {}
    if rule is not None:
        if not plans:
            raise NotImplementedError(
                "a mask by rule exists in the resident dQ kernel only; "
                "`flash_plan(..., backward=True, mask=%r)` says this "
                "call's is not" % (rule,))
        extra = {"rule": rule}
    inputs = [qf, kf, vf] + ([q2f, k2f] if shared else []) + [
        gf, lse, delta]
    dq_shape = jax.ShapeDtypeStruct((B * G, rows, D), q.dtype)

    # One kernel for the whole backward where the plan says so, else dQ
    # by a kernel of its own.
    fused = profile.FLASH_BWD in plans
    if not fused:
        plan = plans[profile.FLASH_DQ]
        bq, bk = plan.block_q, plan.block_k
        bqp = bq // group
        q_im, kv_spec = _q_walk_specs(plan, L, D, group, causal)
        if plan.path == "resident":
            kernel = functools.partial(
                _bwd_dq_resident_kernel, scale=scale, causal=causal, bk=bk,
                bqp=bqp, group=group, **extra)
            scratch = []
        else:
            kernel = functools.partial(
                _bwd_dq_kernel, scale=scale, causal=causal, num_kb=L // bk,
                bqp=bqp, group=group, **({"shared": True} if shared else {}))
            scratch = [pltpu.VMEM((bq, D), jnp.float32)] + (
                [pltpu.VMEM((bq, D2), jnp.float32)] if shared else [])
        q_spec = pl.BlockSpec((None, bq, D), q_im)
        stripe_spec = pl.BlockSpec((None, bq, 8), q_im)
        if shared:
            q2_spec, k2_spec = _q_walk_shared_specs(plan, L, D2, group,
                                                    causal, of_batch)
        dq = _ruled_call(pl.pallas_call(
            kernel,
            name=profile.FLASH_DQ,
            grid=plan.grid,
            in_specs=[q_spec, kv_spec, kv_spec] + (
                [q2_spec, k2_spec] if shared else []) + [
                q_spec, stripe_spec, stripe_spec],
            out_specs=[q_spec, q2_spec] if shared else q_spec,
            out_shape=[dq_shape, dq2_shape] if shared else dq_shape,
            scratch_shapes=scratch,
            compiler_params=_compiler_params(plan),
            interpret=interpret,
        ), profile.FLASH_DQ, rule, plan, inputs, scale, interpret)(*inputs)
        if shared:
            dq, dq2 = dq

    plan = plans[profile.FLASH_BWD if fused else profile.FLASH_DKV]
    bq, bk = plan.block_q, plan.block_k
    bqp = bq // group
    if plan.held == "q":
        # The one kernel on dQ's grid: a q block a step; k, v, dk, dv whole.
        kernel = functools.partial(_bwd_q_held_kernel, scale=scale,
                                   causal=causal, bk=bk, bqp=bqp,
                                   group=group, **extra,
                                   **({} if rule is None
                                      else {"cut_k": plan.cut_k}))
        q_im, k_spec = _q_walk_specs(plan, L, D, group, causal)
        q_spec = pl.BlockSpec((None, bq, D), q_im)
        stripe_spec = pl.BlockSpec((None, bq, 8), q_im)
        # dK's and dV's accumulators across the q blocks of a (batch, kv
        # head; under a rule dQ's of the block too (the walk's loops carry
        # nothing).
        scratch = [pltpu.VMEM((L, D), jnp.float32),
                   pltpu.VMEM((L, D), jnp.float32)] + (
                       [] if rule is None
                       else [pltpu.VMEM((bq, D), jnp.float32)])
        if shared:
            # The whole-sequence operands in ONE buffer each (`_kernel_plan`
            # counts them so): a head's k and v wait for their copy, 4 MiB
            # in a kernel of half a millisecond a head. The shared key and
            # its gradient by their BATCH: fetched, and written, once for
            # all its heads, summed over them in the third accumulator.
            kernel = functools.partial(kernel, heads=G)
            once = pl.Buffered(1)
            k_spec = pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0),
                                  pipeline_mode=once)
            q2_spec = pl.BlockSpec((None, bq, D2), q_im)
            k2_spec = dk2_spec = pl.BlockSpec(
                (None, L, D2), lambda b, i: (of_batch(b), 0, 0),
                pipeline_mode=once)
            dk2_heads = B
            scratch.append(pltpu.VMEM((L, D2), jnp.float32))
    elif plan.path == "resident":
        kernel = functools.partial(_bwd_dkv_resident_kernel, scale=scale,
                                   causal=causal, bq=bq, bqp=bqp,
                                   group=group, with_dq=fused, **extra)
        k_im = lambda b, j: (b, j, 0)                       # noqa: E731
        q_spec = pl.BlockSpec((None, rows, D), lambda b, j: (b, 0, 0))
        q2_spec = pl.BlockSpec((None, rows, D2), lambda b, j: (b, 0, 0))
        stripe_spec = pl.BlockSpec((None, rows, 8), lambda b, j: (b, 0, 0))
        k_spec = pl.BlockSpec((None, bk, D), k_im)
        # This head's block of the shared key, and its part of the gradient.
        k2_spec = pl.BlockSpec((None, bk, D2),
                               lambda b, j: (of_batch(b), j, 0))
        dk2_spec = pl.BlockSpec((None, bk, D2), k_im)
        dk2_heads = B * G
        # dQ's accumulator across the k blocks of a (batch, kv head).
        scratch = [pltpu.VMEM((rows, D), jnp.float32)] if fused else []
        if fused and shared:
            scratch.append(pltpu.VMEM((rows, D2), jnp.float32))
    else:
        kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, num_qb=rows // bq,
                                   bqp=bqp, group=group, **extra)
        k_im = lambda b, j, i: (b, j, 0)                    # noqa: E731
        q_im = _q_index_map(bqp, bk, causal) if rule is None \
            else _rule_q_index_map(rule, bqp, bk, L)
        q_spec = pl.BlockSpec((None, bq, D), q_im)
        stripe_spec = pl.BlockSpec((None, bq, 8), q_im)
        k_spec = pl.BlockSpec((None, bk, D), k_im)
        q2_spec = pl.BlockSpec((None, bq, D2), q_im)
        k2_spec = pl.BlockSpec((None, bk, D2),
                               lambda b, j, i: (of_batch(b), j, 0))
        dk2_spec = pl.BlockSpec((None, bk, D2), k_im)
        dk2_heads = B * G
        scratch = [pltpu.VMEM((bk, D), jnp.float32),
                   pltpu.VMEM((bk, D), jnp.float32)] + (
                       [pltpu.VMEM((bk, D2), jnp.float32)] if shared else [])
    # A result whose block does not change across the grid's block axis (the
    # one kernel's dQ, or held by the q block dK and dV) is written back once.
    results = _ruled_call(pl.pallas_call(
        kernel,
        name=profile.FLASH_BWD if fused else profile.FLASH_DKV,
        grid=plan.grid,
        in_specs=[q_spec, k_spec, k_spec] + (
            [q2_spec, k2_spec] if shared else []) + [
            q_spec, stripe_spec, stripe_spec],
        # Results in the kernel's order: dk, dv, [dk2], [dq, [dq2]].
        out_specs=[k_spec, k_spec] + ([dk2_spec] if shared else []) + (
            [q_spec] + ([q2_spec] if shared else []) if fused else []),
        out_shape=[
            jax.ShapeDtypeStruct((B * G, L, D), k.dtype),
            jax.ShapeDtypeStruct((B * G, L, D), v.dtype),
        ] + ([jax.ShapeDtypeStruct((dk2_heads, L, D2), k2f.dtype)]
             if shared else []) + (
            [dq_shape] + ([dq2_shape] if shared else []) if fused else []),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(
            plan, carries=fused,
            heads_in_order=bool(shared) and plan.held == "q"),
        interpret=interpret,
    ), profile.FLASH_BWD if fused else profile.FLASH_DKV, rule, plan, inputs,
        scale, interpret)(*inputs)
    if shared:
        dk, dv, dk2, *rest = results
        if fused:
            dq, dq2 = rest
        if dk2_heads == B:  # summed over the heads where it was formed
            dk2 = dk2.reshape(B, 1, L, D2)
        else:  # a head's part each: summed in f32
            dk2 = jnp.sum(dk2.reshape(B, G, L, D2), axis=1, keepdims=True,
                          dtype=jnp.float32).astype(dk2.dtype)
        return (_from_rows(dq, B, group), dk.reshape(B, G, L, D),
                dv.reshape(B, G, L, D), _from_rows(dq2, B, group), dk2)
    if fused:
        dk, dv, dq = results
    else:
        dk, dv = results

    return (_from_rows(dq, B, group), dk.reshape(B, G, L, D),
            dv.reshape(B, G, L, D))


def _blockwise_reference(q, k, v, scale, causal, rule=None):
    """Blockwise JAX attention, O(BLOCK_Q * L) live memory; used for the
    backward recompute and as the non-TPU fallback. q [B,H,L,D], k/v
    [B,G,L,D] — GQA repeats kv across each head group here (the kernel
    path never materializes that). ``rule``: a mask by rule in place of
    ``causal``."""
    B, H, L, D = q.shape
    G = k.shape[1]
    group = H // G
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    block_q = min(BLOCK_Q, L)

    def per_qblock(start, size):
        qs = lax.slice_in_dim(q, start, start + size, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if rule is not None:
            rows = start + lax.broadcasted_iota(jnp.int32, (size, 1), 0)
            cols = lax.broadcasted_iota(jnp.int32, (1, L), 1)
            s = jnp.where(rule.visible(rows, cols)[None, None], s, -jnp.inf)
        elif causal:
            rows = start + lax.broadcasted_iota(jnp.int32, (size, L), 0)
            cols = lax.broadcasted_iota(jnp.int32, (size, L), 1)
            s = jnp.where((rows >= cols)[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    # Ceil-divide over q so a sequence remainder (L % block_q != 0) gets
    # its own (smaller, still static-shaped) tail block.
    blocks = [per_qblock(start, min(block_q, L - start))
              for start in range(0, L, block_q)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, interpret, rule=None):
    if interpret is None:
        return _blockwise_reference(q, k, v, scale, causal, rule)
    return _pallas_forward_lse(q, k, v, scale, causal, interpret,
                               rule=rule)[0]


def _flash_fwd(q, k, v, scale, causal, interpret, rule=None):
    if interpret is None:
        return (_blockwise_reference(q, k, v, scale, causal, rule),
                (q, k, v, None, None))
    out, lse = _pallas_forward_lse(q, k, v, scale, causal, interpret,
                                   rule=rule)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, interpret, rule, res, g):
    q, k, v, out, lse = res
    if interpret is None:
        # Non-kernel path: recompute-blockwise VJP in plain JAX.
        _, vjp = jax.vjp(
            lambda q, k, v: _blockwise_reference(q, k, v, scale, causal,
                                                 rule),
            q, k, v)
        return vjp(g)
    return _pallas_backward(q, k, v, out, lse, g, scale, causal,
                            interpret, rule=rule)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _blockwise_shared(q, k, v, q2, k2, scale, causal):
    """The blockwise jnp form of scores of two products: the two pairs side
    by side, the shared key repeated over the heads (HERE only: the kernels
    never do)."""
    H, G = q.shape[1], k.shape[1]
    return _blockwise_reference(
        jnp.concatenate([q, q2], axis=-1),
        jnp.concatenate([jnp.repeat(k, H // G, axis=1),
                         jnp.repeat(k2, H, axis=1)], axis=-1),
        jnp.repeat(v, H // G, axis=1), scale, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_shared(q, k, v, q2, k2, scale, causal, interpret):
    """`_flash` with scores of two products: q2 [B,H,L,D2] on the one key
    k2 [B,1,L,D2] of every head beside q on k."""
    if interpret is None:
        return _blockwise_shared(q, k, v, q2, k2, scale, causal)
    return _pallas_forward_lse(q, k, v, scale, causal, interpret,
                               shared=(q2, k2))[0]


def _flash_shared_fwd(q, k, v, q2, k2, scale, causal, interpret):
    if interpret is None:
        return (_blockwise_shared(q, k, v, q2, k2, scale, causal),
                (q, k, v, q2, k2, None, None))
    out, lse = _pallas_forward_lse(q, k, v, scale, causal, interpret,
                                   shared=(q2, k2))
    return out, (q, k, v, q2, k2, out, lse)


def _flash_shared_bwd(scale, causal, interpret, res, g):
    q, k, v, q2, k2, out, lse = res
    if interpret is None:
        _, vjp = jax.vjp(
            lambda *a: _blockwise_shared(*a, scale, causal), q, k, v, q2, k2)
        return vjp(g)
    return _pallas_backward(q, k, v, out, lse, g, scale, causal, interpret,
                            shared=(q2, k2))


_flash_shared.defvjp(_flash_shared_fwd, _flash_shared_bwd)


def analytic_attention_flops(B, H, L, D, causal=True, training=False):
    """FLOPs the Pallas attention kernels execute per call — XLA's
    compiled-cost analysis reports custom calls as ZERO flops, so
    benchmarks add this analytic count to keep MFU honest. Forward runs
    2 matmuls per (q,k) block pair (QK^T, PV); the two backward kernels
    run 7 matmul-equivalents (s and dp are recomputed in both the dQ and
    dK/dV kernels, plus the dQ/dK/dV products). ``training=True``
    therefore returns the FULL forward+backward step count (2 + 7 = 9
    per block pair) — callers must NOT add a separate forward term.
    Where the backward is one kernel (`flash_plan` names it
    `hvd_flash_bwd`) it executes 5, s and dp formed once: 2 + 5 = 7, so
    this count is then an upper bound. Causal halves the visited block
    pairs. H is the number of QUERY heads — GQA/MQA change kv memory
    traffic, not attention FLOPs."""
    per_matmul = 2.0 * B * H * L * L * D
    if causal:
        per_matmul /= 2.0
    return (9.0 if training else 2.0) * per_matmul


def flash_attention(q, k, v, causal=True, scale=None, q_shared=None,
                    k_shared=None, mask=None):
    """Flash attention over [B, L, H, D] inputs (same layout as
    `parallel.ring.ring_attention`); returns [B, L, H, D] in q.dtype.

    GQA/MQA: pass k/v with fewer heads, [B, L, G, D] with G dividing H
    — query head h attends through kv head h // (H // G) (consecutive
    query heads share a kv head, the llama convention). Rotary embedding is
    the caller's: q and k arrive rotated.

    Scores of two products (latent attention): ``q_shared`` [B, L, H, D2]
    and ``k_shared`` [B, L, 1, D2], ONE key a position for every head; the
    scores are ``scale * (q.k + q_shared.k_shared)``, ``scale`` by default
    (D + D2) ** -0.5, and v is as wide as k. The kernels read the shared
    key by its batch and never repeat it over the heads in memory.

    ``mask``: a rule over (query position, key position) in place of
    ``causal`` (`BlockDiffusionMask(length, block)`, with L = 2 x length:
    a noisy and a clean copy of a sequence under block-diffusion training's
    mask; `BandMask(window)`: a query on itself and the window - 1 keys
    before it). The kernels compute the tiles the rule leaves non-empty and
    mask only those it cuts (`flash_plan(..., mask=)` counts them); the second
    score product is refused beside it.

    L must be a multiple of 128 to hit the Pallas kernel; other shapes
    (and non-TPU backends without interpret mode) use the blockwise JAX
    fallback, which is numerically identical.
    """
    B, L, H, D = q.shape
    G = k.shape[2]
    if H % G:
        raise ValueError(
            f"num_heads={H} must be a multiple of num_kv_heads={G}")
    group = H // G
    if (q_shared is None) != (k_shared is None):
        raise ValueError("q_shared and k_shared come together")
    D2 = 0 if q_shared is None else q_shared.shape[-1]
    if D2:
        if (k_shared.shape != (B, L, 1, D2) or q_shared.shape[:3] != (B, L, H)
                or v.shape[-1] != D):
            raise ValueError(
                "q_shared %s / k_shared %s / v %s: want [B, L, H, D2], "
                "[B, L, 1, D2] and v as wide as k"
                % (q_shared.shape, k_shared.shape, v.shape))
    if mask is not None:
        if D2:
            raise ValueError("mask=%r cannot be combined with q_shared / "
                             "k_shared" % (mask,))
        mask.check(L, 1, 1)
    if scale is None:
        scale = (D + D2) ** -0.5
    # Kernel layout: [B, H, L, D] / [B, G, L, D].
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    on_tpu = jax.default_backend() == "tpu"
    if mask is not None:
        # The rule's kernels tile what it says; no plan, no kernel.
        kernel_ok = on_tpu and mask.tiled(L) % BLOCK_Q == 0 and all(
            flash_plan(B, H, L, D, group, q.dtype, backward, mask=mask)
            for backward in (False, True))
        out = _flash(qt, kt, vt, scale, False, False if kernel_ok else None,
                     mask)
        return out.transpose(0, 2, 1, 3)
    kernel_ok = (
        on_tpu and L % BLOCK_Q == 0 and
        _pick_rows_block(L, _grouped_blocks(D, L, group)[0], group)
        is not None and _pick_rows_block(
            L, _grouped_blocks(D, L, group, backward=True)[0], group)
        is not None)
    if D2:
        out = _flash_shared(qt, kt, vt, q_shared.transpose(0, 2, 1, 3),
                            k_shared.transpose(0, 2, 1, 3), scale, causal,
                            False if kernel_ok else None)
        return out.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, scale, causal, False if kernel_ok else None)
    return out.transpose(0, 2, 1, 3)
