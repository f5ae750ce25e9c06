"""Long-context sequence parallelism (ring attention, Ulysses) and the
compressed ring allreduce.

Not present in the reference (SURVEY.md §5.7 — it never sees activations);
first-class here because long context shapes the core design on TPU.

* :func:`ring_allreduce` — explicit ``lax.ppermute`` ring allreduce with
  EQuARX-style wire compression fused into the per-hop compute
  (quantize/dequantize as part of each hop, not a pre/post pass), for
  gradient bytes on the ICI/DCN links (docs/COMPRESSION.md).

* :func:`ring_attention` — blockwise (flash-style) attention where each
  device holds a sequence shard and k/v blocks rotate around the ICI ring
  via ``lax.ppermute``; compute on the current block overlaps the
  neighbour exchange (XLA schedules the ppermute concurrently with the
  matmuls since there is no data dependence until the next iteration).
  Softmax is accumulated online (running max + normaliser), so the result
  is exact full attention over the whole sequence at O(L/n) memory.
* :func:`ulysses_attention` — all-to-all alternative: reshard from
  sequence-sharded to head-sharded, run dense local attention, reshard
  back. Better when heads >= devices and the per-device sequence is short.

Both support GQA/MQA (k/v with fewer heads than q: [B, L, G, D] with
G | H). Rotary embedding is the caller's, from the GLOBAL positions of its
shard's tokens (`models.transformer` does it), before either is called.

Both are meant to run inside ``shard_map`` over a mesh axis (see
`horovod_tpu.parallel.mesh.hybrid_mesh`).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _block_attention(q, k, v, o, m, l, q_offset, kv_offset, causal, scale):
    """One flash-attention block update with online softmax.

    q [B,Lq,H,D]; k,v [B,Lk,H,D]; o [B,Lq,H,D] f32 accumulator;
    m,l [B,H,Lq] running max / normaliser. Offsets are *global* token
    offsets of the local q block and the current k/v block, for causal
    masking across devices.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        k_pos = kv_offset + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp(-inf - -inf) guard: a fully-masked row keeps m == -inf; correct
    # the scale factor to 0 there instead of NaN.
    alpha = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(m - m_new))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0, p)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def _interpret_mode():
    """HVD_TPU_PALLAS_INTERPRET=1 runs the ring kernel in Pallas
    interpret mode on any backend (test coverage of the kernel path
    without TPU hardware)."""
    import os
    return os.environ.get("HVD_TPU_PALLAS_INTERPRET", "0") == "1"


def _use_flash_ring(Lq, Lk, scale):
    """The Pallas carry-state kernel needs 128-aligned sequence shards
    (any head dim: blocks span the full D), a static scale (the kernel
    closes over it), and a TPU default backend. The backend check is a
    heuristic: a CPU mesh built on a TPU-attached host would be
    misrouted for aligned shards — set HVD_TPU_RING_KERNEL=0 to force
    the jnp path there (or HVD_TPU_PALLAS_INTERPRET=1 to run the kernel
    in interpret mode anywhere)."""
    import os

    if Lq % 128 != 0 or Lk % 128 != 0:
        return False
    if not isinstance(scale, (int, float)):
        return False  # traced scale: the jnp path differentiates it
    if os.environ.get("HVD_TPU_RING_KERNEL", "1") == "0":
        return False
    return jax.default_backend() == "tpu" or _interpret_mode()


def _shard_visible(src, idx, Lq, Lk):
    """Whether the kv shard starting at src*Lk overlaps the causal
    lower triangle of this rank's q rows [idx*Lq, (idx+1)*Lq)."""
    return src * Lk <= idx * Lq + (Lq - 1)


def _causal_skip_step(causal, src, idx, Lq, Lk, step, a, b, c,
                      k_blk, v_blk):
    """Run `step(a, b, c, k_blk, v_blk)` unless the held kv shard is
    entirely in this rank's future on a causal run (then pass the
    carry through untouched). ONE definition for the jnp, kernel-fwd
    and kernel-bwd rings so the predicate cannot desynchronize.

    What this buys: on the jnp ring it skips real masked-einsum FLOPs;
    on the kernel rings the per-block `pl.when` guards already skipped
    the FLOPs, so it skips the pallas_call dispatch, its block DMAs,
    and the carry copies. Either way it is per-rank work/energy, NOT
    ring latency: the schedule is lockstep and rank n-1 computes at
    every step, so the critical path is unchanged — that is what
    `ring_attention(schedule="zigzag")` below fixes."""
    if not causal:
        return step(a, b, c, k_blk, v_blk)
    return lax.cond(_shard_visible(src, idx, Lq, Lk), step,
                    lambda a, b, c, *_: (a, b, c),
                    a, b, c, k_blk, v_blk)


def _ring_jnp(q, k, v, axis_name, causal, scale):
    """Blockwise jnp ring (non-TPU / unaligned-shape fallback).
    q [B,Lq,H,D]; k/v [B,Lk,G,D] — GQA repeats kv across each head
    group (the kernel path never materializes that)."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk, G = k.shape[1], k.shape[2]
    perm = [(j, (j + 1) % n) for j in range(n)]

    step = functools.partial(_block_attention, causal=causal, scale=scale)

    o0 = jnp.zeros((B, Lq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry
        src = (idx - i) % n  # which global block we currently hold

        def compute(o, m, l, k_blk, v_blk):
            if G != H:
                # GQA: repeat the traveling G-head shard up to H just
                # for the local einsum (the ring moves the small one).
                k_blk = jnp.repeat(k_blk, H // G, axis=2)
                v_blk = jnp.repeat(v_blk, H // G, axis=2)
            return step(q, k_blk, v_blk, o, m, l,
                        q_offset=idx * Lq, kv_offset=src * Lk)

        o, m, l = _causal_skip_step(causal, src, idx, Lq, Lk, compute,
                                    o, m, l, k_blk, v_blk)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _to_rows_bl(x, group):
    """[B, L, H, D] (H = G*group) -> grouped kernel layout
    [B*G, L*group, D]; ONE row-ordering definition (the kernel
    module's `_to_rows`) so the ring and plain layouts cannot
    disagree. group=1 is the plain [B*H, L, D] layout."""
    from horovod_tpu.ops.flash_attention import _to_rows
    return _to_rows(x.transpose(0, 2, 1, 3), group)


def _from_rows_bl(x, B, group):
    """Inverse of `_to_rows_bl`: [B*G, L*group, D] -> [B, L, H, D]."""
    from horovod_tpu.ops.flash_attention import _from_rows
    return _from_rows(x, B, group).transpose(0, 2, 1, 3)


def _schedule_offsets(schedule, rank, n, L):
    """Global token offset(s) of the shard held by `rank` (traced).

    contiguous: one chunk at rank*L. zigzag: the sequence is split into
    2n chunks of L/2; rank r holds chunks (r, 2n-1-r) concatenated —
    the causal load-balancing layout (every rank's lower-triangle work
    is equal, so the lockstep ring's critical path halves vs the
    contiguous layout where rank n-1 does all n steps' work)."""
    if schedule == "zigzag":
        Lc = L // 2
        return jnp.stack([rank * Lc, (2 * n - 1 - rank) * Lc])
    return rank * L


def _ring_flash_impl(q, k, v, axis_name, causal, scale,
                     schedule="contiguous"):
    """Pallas ring forward. q [B,Lq,H,D], k/v [B,Lk,G,D]. Returns
    (out [B,Lq,H,D], out_k, lse) where out_k is the normalized output
    in the grouped-rows kernel layout and lse [B*G, Lq*group, 8] is the
    per-row log-sum-exp stripe the backward ring consumes."""
    from horovod_tpu.ops.flash_attention import flash_ring_step

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk, G = k.shape[1], k.shape[2]
    group = H // G
    perm = [(j, (j + 1) % n) for j in range(n)]

    # Transpose once; the ring circulates kernel-layout k/v shards.
    qk = _to_rows_bl(q, group)
    kk = _to_rows_bl(k, 1)
    vk = _to_rows_bl(v, 1)
    rows = Lq * group
    o0 = jnp.zeros((B * G, rows, D), jnp.float32)
    m0 = jnp.full((B * G, rows, 8), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B * G, rows, 8), jnp.float32)

    q_off = _schedule_offsets(schedule, idx, n, Lq)

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry
        src = (idx - i) % n

        def compute(o, m, l, k_blk, v_blk):
            return flash_ring_step(
                qk, k_blk, v_blk, o, m, l,
                q_offset=q_off,
                kv_offset=_schedule_offsets(schedule, src, n, Lk),
                causal=causal, scale=scale,
                interpret=_interpret_mode(), group=group)

        if schedule == "zigzag":
            # Every step has at-or-below-diagonal work by construction
            # (rank r's high chunk sees every kv shard) — that balance
            # IS the point; no step-level skip exists to take.
            o, m, l = compute(o, m, l, k_blk, v_blk)
        else:
            o, m, l = _causal_skip_step(causal, src, idx, Lq, Lk,
                                        compute, o, m, l, k_blk, v_blk)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, kk, vk))
    l1 = jnp.where(l[:, :, :1] == 0.0, 1.0, l[:, :, :1])
    out_k = (o / l1).astype(q.dtype)
    # lse = m + log(l); untouched rows (m == -inf, l == 0) stay -inf.
    lse = jnp.broadcast_to(m[:, :, :1] + jnp.log(l1), m.shape)
    return _from_rows_bl(out_k, B, group), out_k, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, scale, schedule="contiguous"):
    """Pallas ring attention, wrapped in a custom VJP because Pallas
    kernels are not auto-differentiable. The backward is a second ring
    pass (FlashAttention-2 style) over the saved per-row log-sum-exp —
    no forward recompute: dq accumulates locally while dk/dv travel
    around the ring with their k/v shard."""
    return _ring_flash_impl(q, k, v, axis_name, causal, scale, schedule)[0]


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, schedule):
    out, out_k, lse = _ring_flash_impl(q, k, v, axis_name, causal,
                                       scale, schedule)
    return out, (q, k, v, out_k, lse)


def _ring_flash_bwd(axis_name, causal, scale, schedule, res, g):
    from horovod_tpu.ops.flash_attention import flash_ring_bwd_step

    q, k, v, out_k, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk, G = k.shape[1], k.shape[2]
    group = H // G
    perm = [(j, (j + 1) % n) for j in range(n)]

    qk = _to_rows_bl(q, group)
    kk = _to_rows_bl(k, 1)
    vk = _to_rows_bl(v, 1)
    gk = _to_rows_bl(g, group)
    # delta = rowsum(dO * O): one fused XLA pass per shard, reused by
    # every ring step (both backward kernels stream it per q block).
    delta = jnp.broadcast_to(
        jnp.sum(gk.astype(jnp.float32) * out_k.astype(jnp.float32),
                axis=-1, keepdims=True), lse.shape)

    rows = Lq * group
    dq0 = jnp.zeros((B * G, rows, D), jnp.float32)
    dk0 = jnp.zeros((B * G, Lk, D), jnp.float32)
    dv0 = jnp.zeros((B * G, Lk, D), jnp.float32)

    q_off = _schedule_offsets(schedule, idx, n, Lq)

    def body(i, carry):
        dq, k_blk, v_blk, dk, dv = carry
        src = (idx - i) % n

        def compute(dq, dk, dv, k_blk, v_blk):
            return flash_ring_bwd_step(
                qk, k_blk, v_blk, gk, lse, delta, dq, dk, dv,
                q_offset=q_off,
                kv_offset=_schedule_offsets(schedule, src, n, Lk),
                causal=causal, scale=scale,
                interpret=_interpret_mode(), group=group)

        if schedule == "zigzag":
            dq, dk, dv = compute(dq, dk, dv, k_blk, v_blk)
        else:
            dq, dk, dv = _causal_skip_step(causal, src, idx, Lq, Lk,
                                           compute, dq, dk, dv, k_blk,
                                           v_blk)
        # dk/dv ride the ring with their k/v shard; after n steps each
        # shard's gradient arrives back on its home device.
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        dk_nxt = lax.ppermute(dk, axis_name, perm)
        dv_nxt = lax.ppermute(dv, axis_name, perm)
        return dq, k_nxt, v_nxt, dk_nxt, dv_nxt

    dq, _, _, dk, dv = lax.fori_loop(0, n, body, (dq0, kk, vk, dk0, dv0))
    return (_from_rows_bl(dq, B, group).astype(q.dtype),
            _from_rows_bl(dk, B, 1).astype(k.dtype),
            _from_rows_bl(dv, B, 1).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name, causal=True, scale=None,
                   schedule="contiguous"):
    """Exact multi-head attention over a sequence sharded on `axis_name`.

    Args: q of shape [B, L_local, H, D], k/v [B, L_local, G, D] with
    G | H (GQA/MQA: query head h reads kv head h // (H//G); G == H is
    plain MHA) — per-device shards, equal L_local on every device,
    inside shard_map over `axis_name`. Returns [B, L_local, H, D] in
    q.dtype.

    schedule:
      * "contiguous" (default): rank r holds tokens [r*L_local,
        (r+1)*L_local). Causal runs dispatch nothing for kv shards
        entirely in a rank's future (see `_causal_skip_step` for
        exactly what that saves — and what it does not: ring latency
        is set by the last rank, which computes at every step).
      * "zigzag": the global sequence is split into 2n chunks; rank r
        holds chunks (r, 2n-1-r) concatenated (`zigzag_shard` /
        `zigzag_unshard` convert layouts). Every rank then does the
        same amount of causal lower-triangle work at every ring step,
        halving the lockstep critical path at large n. Kernel path
        only (per-block offset arrays; L_local must be a multiple of
        256 so each chunk is 128-aligned).

    On TPU with 128-aligned shards the per-step local compute runs as a
    Pallas flash kernel with carried online-softmax state
    (`horovod_tpu.ops.flash_attention.flash_ring_step`), so per-step
    memory is O(block) instead of the O(Lq * Lk) score matrix; other
    backends/shapes use the blockwise jnp path. Gradients flow on both
    paths; the kernel path's backward is a second ring pass over the
    saved per-row log-sum-exp (FlashAttention-2 style — no forward
    recompute), with dk/dv accumulators riding the ring alongside
    their k/v shard.
    """
    if schedule not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring schedule: {schedule!r}")
    B, Lq, H, D = q.shape
    Lk, G = k.shape[1], k.shape[2]
    if H % G:
        raise ValueError(
            f"num_heads={H} must be a multiple of num_kv_heads={G}")
    if scale is None:
        scale = D ** -0.5
    if schedule == "zigzag":
        if not causal:
            # Non-causal work is already balanced; the zigzag layout
            # buys nothing and only complicates offsets.
            raise ValueError("schedule='zigzag' is a causal load-"
                             "balancing layout; use contiguous for "
                             "non-causal attention")
        if Lq % 256 or Lk % 256:
            raise ValueError(
                f"zigzag needs 256-multiple shard lengths (two "
                f"128-aligned chunks per rank); got Lq={Lq}, Lk={Lk}")
        if not _use_flash_ring(Lq, Lk, scale):
            raise ValueError(
                "schedule='zigzag' runs on the Pallas kernel ring "
                "only (TPU backend, or HVD_TPU_PALLAS_INTERPRET=1, "
                "static scale)")
        return _ring_flash(q, k, v, axis_name, causal, scale, "zigzag")
    if _use_flash_ring(Lq, Lk, scale):
        return _ring_flash(q, k, v, axis_name, causal, scale, "contiguous")
    return _ring_jnp(q, k, v, axis_name, causal, scale)


def zigzag_shard(x, n, axis=1):
    """Re-layout a GLOBAL sequence axis into zigzag device order:
    split into 2n chunks, device r's shard = concat(chunk r,
    chunk 2n-1-r). The result, sharded contiguously over n devices
    (e.g. shard_map in_specs P(axis_name) on `axis`), gives each
    device exactly the layout `ring_attention(schedule='zigzag')`
    expects. Inverse: `zigzag_unshard`."""
    ch = jnp.split(x, 2 * n, axis=axis)
    return jnp.concatenate(
        [jnp.concatenate([ch[r], ch[2 * n - 1 - r]], axis=axis)
         for r in range(n)], axis=axis)


def zigzag_unshard(x, n, axis=1):
    """Inverse of `zigzag_shard` (zigzag device order -> the natural
    global sequence order)."""
    pairs = jnp.split(x, 2 * n, axis=axis)  # [r0, r0', r1, r1', ...]
    out = [None] * (2 * n)
    for r in range(n):
        out[r] = pairs[2 * r]
        out[2 * n - 1 - r] = pairs[2 * r + 1]
    return jnp.concatenate(out, axis=axis)


def ring_allreduce(x, axis_name, compression="none"):
    """Explicit ring allreduce (sum) over `axis_name` with wire
    compression fused into the per-hop compute (EQuARX-style; PAPERS.md
    arxiv 2506.17615). Runs inside shard_map/pmap over a mapped axis.

    The array is flattened and split into one chunk per rank. Phase 1
    (reduce-scatter, n-1 hops): each hop ENCODES the outgoing chunk
    (requant), ships the small payload via ``lax.ppermute``, DECODES the
    incoming one (dequant) and adds it in f32 — the accumulator never
    lives in the narrow format. Phase 2 (allgather, n-1 hops): the owner
    encodes its reduced chunk once, decodes its own copy back (so every
    rank ends with the identical dequantized values), and the encoded
    payload then travels the ring VERBATIM — each hop's ppermute of
    chunk k+1 has no data dependence on the local decode of chunk k, so
    XLA overlaps the dequantize with the neighbor transfer (the
    pipelining trick ring_attention uses for its k/v blocks).

    compression: 'none' | 'bf16' | 'int8' (or a
    `horovod_tpu.compression` mode). bf16 halves the bytes each hop
    moves; int8 cuts them ~3.9x with one f32 scale per 256-element
    block riding in-band (the (q, scales) pair IS the payload). Only
    f32 inputs compress; other dtypes ride 'none'.

    Returns the SUM over the axis in x's dtype/shape (callers divide
    for an average). With compression='none' this is numerically a
    psum (up to f32 sum order); prefer plain psum there — this path
    exists for the compressed modes.
    """
    from horovod_tpu import compression as _comp

    mode = _comp.resolve(compression)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    orig_shape, orig_dtype = x.shape, x.dtype
    if mode.mode != _comp.NONE and orig_dtype != jnp.float32:
        mode = _comp.Compression.none
    # Only the compressed f32 path needs an f32 working copy; degraded
    # dtypes (int32/int64/f64...) stay in their own dtype so large ints
    # and f64 sum exactly, like psum would.
    work_dtype = jnp.float32 if mode.mode != _comp.NONE else orig_dtype
    flat = x.astype(work_dtype).reshape(-1)
    if n == 1:
        return flat.reshape(orig_shape).astype(orig_dtype)
    # Chunk length: rank-uniform, padded to the int8 block so every
    # chunk quantizes on block boundaries.
    c = -(-flat.size // n)
    c = -(-c // _comp.BLOCK) * _comp.BLOCK
    chunks = jnp.pad(flat, (0, n * c - flat.size)).reshape(n, c)
    perm = [(j, (j + 1) % n) for j in range(n)]
    enc, dec, ship = _ring_codec(mode)

    # Reduce-scatter: after n-1 hops this rank's chunk (idx+1)%n holds
    # the full sum. Each hop requantizes the freshly-reduced outgoing
    # chunk and dequant-adds the incoming one in f32.
    def rs_body(s, chunks):
        send_i = (idx - s) % n
        recv_i = (idx - s - 1) % n
        incoming = ship(enc(jnp.take(chunks, send_i, axis=0)), axis_name,
                        perm)
        upd = jnp.take(chunks, recv_i, axis=0) + dec(incoming)
        return lax.dynamic_update_index_in_dim(chunks, upd, recv_i, 0)

    chunks = lax.fori_loop(0, n - 1, rs_body, chunks)

    # Allgather: encode the owned chunk once; every rank decodes the
    # SAME bytes (the owner re-decodes its own copy), so results are
    # rank-identical — no per-hop requantization drift.
    owned = (idx + 1) % n
    payload = enc(jnp.take(chunks, owned, axis=0))
    chunks = lax.dynamic_update_index_in_dim(chunks, dec(payload), owned, 0)

    def ag_body(s, carry):
        chunks, payload = carry
        recv_i = (idx - s) % n
        # ppermute first: the transfer of this hop's payload and the
        # decode of the previous hop's chunk have no data dependence.
        incoming = ship(payload, axis_name, perm)
        chunks = lax.dynamic_update_index_in_dim(chunks, dec(incoming),
                                                 recv_i, 0)
        return chunks, incoming

    chunks, _ = lax.fori_loop(0, n - 1, ag_body, (chunks, payload))
    out = chunks.reshape(-1)[:flat.size]
    return out.reshape(orig_shape).astype(orig_dtype)


def _ring_codec(mode):
    """(enc, dec, ship) hop codec triple shared by the ring collectives
    (one definition so the allreduce and the split-out reduce-scatter /
    allgather legs cannot disagree on the wire format)."""
    from horovod_tpu import compression as _comp

    def enc(v):
        if mode.mode == _comp.BF16:
            return (v.astype(jnp.bfloat16),)
        if mode.mode == _comp.INT8:
            return _comp.quantize_int8_jax(v)
        return (v,)

    def dec(payload):
        if mode.mode == _comp.BF16:
            return payload[0].astype(jnp.float32)
        if mode.mode == _comp.INT8:
            return _comp.dequantize_int8_jax(*payload)
        return payload[0]

    def ship(payload, axis_name, perm):
        return tuple(lax.ppermute(p, axis_name, perm) for p in payload)

    return enc, dec, ship


def ring_reduce_scatter(x, axis_name, compression="none"):
    """Reduce-scatter leg of the ring as a standalone collective
    (docs/ZERO.md): flattens `x`, splits it into one chunk per rank
    (padded so chunks are equal and int8-block-aligned), and after n-1
    ppermute hops returns THIS rank's chunk of the cross-axis SUM — a
    1-D f32 array of ``ceil(size/n)`` (block-rounded) elements. Chunk r
    belongs to axis index r, so ``ring_allgather`` of per-rank results
    reassembles the full vector in order.

    Wire compression ('bf16'/'int8') encodes each hop's payload exactly
    like :func:`ring_allreduce`'s first phase — the accumulator stays
    f32. The chunk length is ``ceil(ceil(size/n)/BLOCK)*BLOCK`` (the
    int8 block padding applies in every mode so a mode change never
    changes shard shapes). With n == 1 returns the (padded) flat vector
    unchanged.
    """
    from horovod_tpu import compression as _comp

    mode = _comp.resolve(compression)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    if mode.mode != _comp.NONE and x.dtype != jnp.float32:
        mode = _comp.Compression.none
    work_dtype = jnp.float32 if mode.mode != _comp.NONE else x.dtype
    flat = x.astype(work_dtype).reshape(-1)
    c = -(-flat.size // n)
    c = -(-c // _comp.BLOCK) * _comp.BLOCK
    if n == 1:
        return jnp.pad(flat, (0, c - flat.size))
    chunks = jnp.pad(flat, (0, n * c - flat.size)).reshape(n, c)
    perm = [(j, (j + 1) % n) for j in range(n)]
    enc, dec, ship = _ring_codec(mode)

    # The allreduce's schedule (send (idx-s), recv (idx-s-1)) leaves
    # rank r owning chunk (r+1)%n; shifting every chunk index by -1
    # leaves rank r owning chunk r — rank order == chunk order, so the
    # matching ring_allgather reassembles the vector without a permute.
    def body(s, chunks):
        send_i = (idx - s - 1) % n
        recv_i = (idx - s - 2) % n
        incoming = ship(enc(jnp.take(chunks, send_i, axis=0)), axis_name,
                        perm)
        upd = jnp.take(chunks, recv_i, axis=0) + dec(incoming)
        return lax.dynamic_update_index_in_dim(chunks, upd, recv_i, 0)

    chunks = lax.fori_loop(0, n - 1, body, chunks)
    return jnp.take(chunks, idx, axis=0)


def ring_allgather(x, axis_name, compression="none"):
    """Allgather leg of the ring as a standalone collective
    (docs/ZERO.md): every rank contributes an equal-shape 1-D shard
    (axis index r's shard is chunk r) and receives the concatenation of
    all of them — the parameter leg of the sharded weight update, where
    XLA can overlap each hop's ppermute with downstream compute on
    already-received chunks.

    With compression, each owner encodes its shard ONCE and decodes its
    own copy back, and the encoded payload travels the ring VERBATIM —
    every rank ends with bitwise-identical values (the allreduce's
    second phase, unchanged). Parameters usually ride 'none': the
    updated weights are the values every rank must agree on exactly.
    """
    from horovod_tpu import compression as _comp

    mode = _comp.resolve(compression)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    if mode.mode != _comp.NONE and x.dtype != jnp.float32:
        mode = _comp.Compression.none
    if n == 1:
        return x.reshape(-1)
    c = x.size
    perm = [(j, (j + 1) % n) for j in range(n)]
    enc, dec, ship = _ring_codec(mode)
    chunks = jnp.zeros((n, c), x.dtype if mode.mode == _comp.NONE
                       else jnp.float32)
    payload = enc(x.reshape(-1).astype(chunks.dtype))
    chunks = lax.dynamic_update_index_in_dim(chunks, dec(payload), idx, 0)

    def body(s, carry):
        chunks, payload = carry
        recv_i = (idx - s - 1) % n
        # ppermute first: the transfer and the previous chunk's decode
        # have no data dependence, so XLA overlaps them.
        incoming = ship(payload, axis_name, perm)
        chunks = lax.dynamic_update_index_in_dim(chunks, dec(incoming),
                                                 recv_i, 0)
        return chunks, incoming

    chunks, _ = lax.fori_loop(0, n - 1, body, (chunks, payload))
    return chunks.reshape(-1)


def ulysses_attention(q, k, v, axis_name, causal=True, scale=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Input q [B, L_local, H, D] / k, v [B, L_local, G, D] sequence-
    sharded; all_to_all turns them into [B, L_full, H/n, D] (and
    [B, L_full, G/n, D]) head-sharded, local flash attention runs on
    the full sequence, and a second all_to_all restores sequence
    sharding. Both H and G must be divisible by the axis size (GQA
    keeps its head grouping because consecutive query heads share a kv
    head and the split is contiguous).
    """
    n = lax.psum(1, axis_name)
    B, Ll, H, D = q.shape
    G = k.shape[2]
    if H % G:
        raise ValueError(
            f"num_heads={H} must be a multiple of num_kv_heads={G}")
    if H % n or G % n:
        raise ValueError(
            f"ulysses needs the sp axis size ({n}) to divide both "
            f"num_heads={H} and num_kv_heads={G} (the all_to_all "
            f"splits the head dims)")
    if scale is None:
        scale = D ** -0.5

    def seq_to_heads(x):
        # [B, Ll, H, D] -> concat seq, split heads -> [B, Ll*n, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # Local attention over the full sequence: flash_attention keeps it
    # O(L) memory on TPU (custom VJP covers the backward) and itself
    # falls back to the numerically-identical blockwise implementation
    # on other backends/unaligned shapes.
    from horovod_tpu.ops import flash_attention
    og = flash_attention(qg, kg, vg, causal=causal, scale=scale)
    return heads_to_seq(og)
