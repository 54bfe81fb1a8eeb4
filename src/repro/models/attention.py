"""Attention paths.

Three implementations, chosen by shape/mesh (see repro.distributed.sharding):

* ``dense_attention`` — reference/small-shape path (heads sharded over
  'model' when divisible).
* ``blockwise_attention`` — memory-efficient online-softmax scan over KV
  blocks; used for long prefill / training where the sequence dimension is
  sharded ('model' sequence parallelism). Works for any head count.
* ``paged_decode_attention`` — the Libra fast path: anchored KV pages are
  read in place via block-table metadata; each chip attends over the pages
  it owns and partial softmax statistics are combined across the combine
  axes (flash-decode). Implemented with shard_map; the Pallas kernel in
  repro.kernels.paged_attention computes the same per-chip partials on TPU.

Mechanism/policy split (the paper's core design): the device functions here
are pure *mechanisms* — every placement decision (which page, which shard,
which slot/offset, each page's base position) arrives as explicit int32
metadata from the control plane, exactly as Libra's eBPF programs feed the
kernel data plane. This also makes ring-buffer (sliding-window) pages free:
the engine just reuses slots and updates ``page_pos``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B, Sq, Hkv, G, hd], k [B, Skv, Hkv, hd] -> [B, Hkv, G, Sq, Skv]."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def _mask_bias(pos_q, pos_kv, causal: bool, window) -> jax.Array:
    """pos_q [B, Sq], pos_kv [B, Skv] -> additive bias [B, 1, 1, Sq, Skv].

    ``window`` may be a traced scalar (<=0 means no windowing) so that a
    per-layer window array can ride through lax.scan.
    """
    dq = pos_q[:, :, None]
    dk = pos_kv[:, None, :]
    ok = jnp.ones((dq.shape[0], dq.shape[1], dk.shape[2]), bool)
    if causal:
        ok = ok & (dq >= dk)
    window = jnp.asarray(window)
    ok = ok & ((window <= 0) | (dq - dk < window))
    return jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos_q: jax.Array,
    pos_kv: jax.Array,
    *,
    causal: bool = True,
    window=0,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference attention. q [B,Sq,Hq,hd], k/v [B,Skv,Hkv,hd]."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = (q * (1.0 / math.sqrt(hd))).reshape(b, sq, hkv, g, hd)
    scores = _gqa_scores(qg, k)  # [B,Hkv,G,Sq,Skv]
    scores = scores + _mask_bias(pos_q, pos_kv, causal, window)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, hd)


def _block_pairs(nq: int, nkv: int, causal: bool, window_blocks: int):
    """Statically enumerate the (q_chunk, kv_block) pairs that can contain
    unmasked entries. This is how the implementation keeps HLO FLOPs equal
    to the *useful* attention FLOPs: masked-out blocks are never emitted,
    so causal attention costs exactly n(n+1)/2 block matmuls and windowed
    attention only its band — no 2x rectangle waste in the roofline."""
    pairs = []
    for i in range(nq):
        for j in range(nkv):
            if causal and j > i:
                continue
            if window_blocks > 0 and j < i - window_blocks:
                continue
            pairs.append((i, j))
    return pairs


def blockwise_attention(q, k, v, pos_q, pos_kv, *, causal=True, window=0,
                        q_chunk=512, kv_chunk=512):
    """Keyword-friendly wrapper over the custom-VJP implementation."""
    return _blockwise_cv(q, k, v, pos_q, pos_kv, causal, int(window),
                         q_chunk, kv_chunk)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _blockwise_cv(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos_q: jax.Array,
    pos_kv: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 512,
) -> jax.Array:
    """Flash-structured online-softmax attention over (q_chunk × kv_block)
    tiles (never materialises [Sq, Skv]).

    A single lax.scan runs over the statically-enumerated valid tile list;
    per step it updates the running (m, l, acc) slice of its q chunk. With
    the q sequence dim sharded over 'model' this is sequence-parallel
    attention with no head-count divisibility requirement. ``window`` must
    be a Python int here (block enumeration is static); per-layer windows
    are handled by the caller grouping layers.

    The backward pass is a custom VJP that RECOMPUTES each tile's scores
    from (q, k, lse) — flash-attention backward. Without it, autodiff
    stashes every tile's score matrix ([n_pairs, B, H, Sq/c, c] — 1.2 GB
    per layer for phi3@4k) and that stash dominated the training-memory
    roofline term (EXPERIMENTS §Perf hillclimb).
    """
    out, _lse = _blockwise_fwd_impl(q, k, v, pos_q, pos_kv, causal, window,
                                    q_chunk, kv_chunk)
    return out


def _blockwise_fwd_impl(q, k, v, pos_q, pos_kv, causal, window, q_chunk,
                        kv_chunk):
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = -(-sq // q_chunk), -(-skv // kv_chunk)
    pad_q = nq * q_chunk - sq
    pad_kv = nkv * kv_chunk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        pos_q = jnp.pad(pos_q, ((0, 0), (0, pad_q)), constant_values=-(2**30))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        pos_kv = jnp.pad(pos_kv, ((0, 0), (0, pad_kv)), constant_values=2**30)
    sq_p, skv_p = nq * q_chunk, nkv * kv_chunk
    qg = (q * (1.0 / math.sqrt(hd))).reshape(b, sq_p, hkv, g, hd)

    wblocks = -(-window // kv_chunk) + 1 if window > 0 else 0
    pairs = _block_pairs(nq, nkv, causal, wblocks)
    pair_arr = jnp.array(pairs, jnp.int32)  # [n_pairs, 2]

    def body(carry, pair):
        m, l, acc = carry  # [B,H,G,Sq], [B,H,G,Sq], [B,H,G,Sq,hd]
        i, j = pair[0], pair[1]
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, 1)
        pq = jax.lax.dynamic_slice_in_dim(pos_q, i * q_chunk, q_chunk, 1)
        kb = jax.lax.dynamic_slice_in_dim(k, j * kv_chunk, kv_chunk, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, j * kv_chunk, kv_chunk, 1)
        pb = jax.lax.dynamic_slice_in_dim(pos_kv, j * kv_chunk, kv_chunk, 1)
        s = _gqa_scores(qb, kb) + _mask_bias(pq, pb, causal, window)  # [B,H,G,cq,ck]
        m_i = jax.lax.dynamic_slice_in_dim(m, i * q_chunk, q_chunk, 3)
        l_i = jax.lax.dynamic_slice_in_dim(l, i * q_chunk, q_chunk, 3)
        a_i = jax.lax.dynamic_slice_in_dim(acc, i * q_chunk, q_chunk, 3)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        a_new = a_i * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb
        ).astype(jnp.float32)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * q_chunk, 3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * q_chunk, 3)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, i * q_chunk, 3)
        return (m, l, acc), None

    m0 = jnp.full((b, hkv, g, sq_p), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq_p), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq_p, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), pair_arr)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq_p, hq, hd)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,Hkv,G,Sq_p]
    return out[:, :sq].astype(q.dtype), lse


def _blockwise_fwd(q, k, v, pos_q, pos_kv, causal, window, q_chunk, kv_chunk):
    out, lse = _blockwise_fwd_impl(q, k, v, pos_q, pos_kv, causal, window,
                                   q_chunk, kv_chunk)
    return out, (q, k, v, pos_q, pos_kv, out, lse)


def _blockwise_bwd(causal, window, q_chunk, kv_chunk, res, dout):
    """Flash backward: recompute tile scores from (q, k, lse); accumulate
    dq/dk/dv per tile. Nothing tile-sized is ever saved."""
    q, k, v, pos_q, pos_kv, out, lse = res
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = -(-sq // q_chunk), -(-skv // kv_chunk)
    pad_q = nq * q_chunk - sq
    pad_kv = nkv * kv_chunk - skv
    scale = 1.0 / math.sqrt(hd)

    def padq(x, fill=0.0):
        return jnp.pad(x, ((0, 0), (0, pad_q)) + ((0, 0),) * (x.ndim - 2),
                       constant_values=fill) if pad_q else x

    def padkv(x, fill=0.0):
        return jnp.pad(x, ((0, 0), (0, pad_kv)) + ((0, 0),) * (x.ndim - 2),
                       constant_values=fill) if pad_kv else x

    qp = padq(q)
    kp, vp = padkv(k), padkv(v)
    pos_qp = padq(pos_q, -(2 ** 30))
    pos_kvp = padkv(pos_kv, 2 ** 30)
    doutp = padq(dout)
    outp = padq(out)
    sq_p, skv_p = nq * q_chunk, nkv * kv_chunk

    qg = (qp * scale).reshape(b, sq_p, hkv, g, hd)
    dog = doutp.reshape(b, sq_p, hkv, g, hd)
    og = outp.reshape(b, sq_p, hkv, g, hd)
    # D_i = rowsum(dout * out)
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)  # [B,Hkv,G,Sq]

    wblocks = -(-window // kv_chunk) + 1 if window > 0 else 0
    pair_arr = jnp.array(_block_pairs(nq, nkv, causal, wblocks), jnp.int32)

    def body(carry, pair):
        dq, dk, dv = carry
        i, j = pair[0], pair[1]
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, 1)
        pq = jax.lax.dynamic_slice_in_dim(pos_qp, i * q_chunk, q_chunk, 1)
        dob = jax.lax.dynamic_slice_in_dim(dog, i * q_chunk, q_chunk, 1)
        lse_i = jax.lax.dynamic_slice_in_dim(lse, i * q_chunk, q_chunk, 3)
        dl_i = jax.lax.dynamic_slice_in_dim(delta, i * q_chunk, q_chunk, 3)
        kb = jax.lax.dynamic_slice_in_dim(kp, j * kv_chunk, kv_chunk, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, j * kv_chunk, kv_chunk, 1)
        pb = jax.lax.dynamic_slice_in_dim(pos_kvp, j * kv_chunk, kv_chunk, 1)
        s = _gqa_scores(qb, kb) + _mask_bias(pq, pb, causal, window)
        p = jnp.exp(s - lse_i[..., None])                       # [B,H,G,cq,ck]
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", dob.astype(jnp.float32),
                        vb.astype(jnp.float32))
        ds = p * (dp - dl_i[..., None])                         # f32
        dqb = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb.astype(jnp.float32)) * scale
        dkb = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qb.astype(jnp.float32))
        dvb = jnp.einsum("bhgqk,bqhgd->bkhd", p, dob.astype(jnp.float32))
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, i * q_chunk, q_chunk, 1)
            + dqb.reshape(b, q_chunk, hq, hd), i * q_chunk, 1)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, j * kv_chunk, kv_chunk, 1)
            + dkb, j * kv_chunk, 1)
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, j * kv_chunk, kv_chunk, 1)
            + dvb, j * kv_chunk, 1)
        return (dq, dk, dv), None

    dq0 = jnp.zeros((b, sq_p, hq, hd), jnp.float32)
    dk0 = jnp.zeros((b, skv_p, hkv, hd), jnp.float32)
    dv0 = jnp.zeros((b, skv_p, hkv, hd), jnp.float32)
    (dq, dk, dv), _ = jax.lax.scan(body, (dq0, dk0, dv0), pair_arr)
    return (dq[:, :sq].astype(q.dtype), dk[:, :skv].astype(k.dtype),
            dv[:, :skv].astype(v.dtype), None, None)


_blockwise_cv.defvjp(_blockwise_fwd, _blockwise_bwd)


# ---------------------------------------------------------------------------
# Libra fast path: paged decode attention over anchored pages
# ---------------------------------------------------------------------------

def plan_decode_sharding(global_batch: int, mesh: Mesh) -> Tuple[Optional[object], Tuple[str, ...]]:
    """Decide batch sharding axis + softmax combine axes for decode.

    Requests are sharded over the data axes when divisible; each request's
    pages stripe over the remaining (combine) axes and partial softmax
    stats are psum-combined — flash-decode. Tiny batches (long_500k)
    replicate the batch and stripe pages over every axis.
    """
    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dsize = math.prod([sizes[a] for a in data_axes]) if data_axes else 1
    if data_axes and global_batch % dsize == 0:
        return (data_axes if len(data_axes) > 1 else data_axes[0],
                ("model",) if "model" in sizes else ())
    return None, tuple(mesh.axis_names)


def num_combine_shards(mesh: Mesh, combine_axes: Tuple[str, ...]) -> int:
    sizes = dict(mesh.shape)
    return math.prod([sizes[a] for a in combine_axes]) if combine_axes else 1


def _combined_axis_index(axes: Tuple[str, ...]):
    if not axes:
        return 0
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def paged_decode_attention(
    q: jax.Array,            # [B, Hq, hd]
    k_new: jax.Array,        # [B, Hkv, hd] current token's K
    v_new: jax.Array,        # [B, Hkv, hd]
    pool: jax.Array,         # [P, page, 2, Hkv, hd] anchored pages (sharded on P)
    block_tables: jax.Array, # [B, n_shards, pages_per_shard] local page ids, -1 invalid
    page_pos: jax.Array,     # [B, n_shards, pages_per_shard] base position of each page
    seq_lens: jax.Array,     # [B] position of the incoming token (0-indexed)
    write_shard: jax.Array,  # [B] shard owning the incoming token's page
    write_slot: jax.Array,   # [B] table slot of that page
    *,
    mesh: Mesh,
    batch_axis,
    combine_axes: Tuple[str, ...],
    window=0,
) -> Tuple[jax.Array, jax.Array]:
    """Write the new token's KV into its anchored page, then attend over all
    anchored pages in place. Returns (attn_out [B,Hq,hd], updated pool).

    All placement metadata is control-plane supplied (Libra's mechanism /
    policy split); windowed layers just get ring-buffer tables + page_pos.
    """
    page_size = pool.shape[1]
    bspec = P(batch_axis)
    pool_spec = P(tuple(mesh.axis_names))

    def local(q, k_new, v_new, pool, tables, page_pos, seq_lens, wshard, wslot):
        midx = _combined_axis_index(combine_axes)
        b, hq, hd = q.shape
        hkv = k_new.shape[1]
        g = hq // hkv
        pps = tables.shape[2]

        # ---- write the incoming token's KV into its page (owner only) ----
        owner_rows = tables[jnp.arange(b), wshard]           # [B, pps]
        local_pid = jnp.take_along_axis(owner_rows, wslot[:, None], axis=1)[:, 0]
        pos_rows = page_pos[jnp.arange(b), wshard]
        base = jnp.take_along_axis(pos_rows, wslot[:, None], axis=1)[:, 0]
        off = seq_lens - base
        ok = (wshard == midx) & (local_pid >= 0) & (off >= 0) & (off < page_size)
        write_pid = jnp.where(ok, local_pid, pool.shape[0])
        kv_stack = jnp.stack([k_new, v_new], axis=1)          # [B, 2, Hkv, hd]
        pool = pool.at[write_pid, jnp.clip(off, 0, page_size - 1)].set(
            kv_stack.astype(pool.dtype), mode="drop")

        # ---- attend over locally-owned pages ----
        tbl = tables[:, midx, :]                              # [B, pps]
        ppos = page_pos[:, midx, :]                           # [B, pps]
        pages = pool[jnp.clip(tbl, 0)]                        # [B, pps, page, 2, Hkv, hd]
        kk = pages[:, :, :, 0].reshape(b, pps * page_size, hkv, hd)
        vv = pages[:, :, :, 1].reshape(b, pps * page_size, hkv, hd)
        pos = ppos[:, :, None] + jnp.arange(page_size)[None, None, :]
        w = jnp.asarray(window)
        valid = (tbl[:, :, None] >= 0) & (pos <= seq_lens[:, None, None])
        valid = valid & ((w <= 0) | (seq_lens[:, None, None] - pos < w))
        valid = valid.reshape(b, pps * page_size)

        # keep both einsum inputs in the pool dtype: mixed-precision inputs
        # make XLA pre-convert the WHOLE pool to f32 (8+ GB of traffic at
        # production scale); bf16 x bf16 -> f32 accumulate is MXU-native.
        qg = (q * (1.0 / math.sqrt(hd))).reshape(b, hkv, g, hd).astype(kk.dtype)
        s = jnp.einsum("bhgd,bthd->bhgt", qg, kk, preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_p = jnp.max(s, axis=-1)                             # [B,Hkv,G]
        p = jnp.exp(s - m_p[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_p = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhgt,bthd->bhgd", p.astype(vv.dtype), vv).astype(jnp.float32)

        # ---- combine partial softmax stats across combine axes ----
        if combine_axes:
            m_g = jax.lax.pmax(m_p, combine_axes)
            scale = jnp.exp(m_p - m_g)
            l_g = jax.lax.psum(l_p * scale, combine_axes)
            acc_g = jax.lax.psum(acc * scale[..., None], combine_axes)
        else:
            l_g, acc_g = l_p, acc
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(b, hq, hd).astype(q.dtype), pool

    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(bspec, bspec, bspec, pool_spec, bspec, bspec, bspec, bspec, bspec),
        out_specs=(bspec, pool_spec),
        check_vma=False,
    )
    return shard(q, k_new, v_new, pool, block_tables, page_pos, seq_lens,
                 write_shard, write_slot)


def prefill_write_pages(
    k: jax.Array,            # [B, S, Hkv, hd]
    v: jax.Array,
    pool: jax.Array,         # [P, page, 2, Hkv, hd]
    block_tables: jax.Array, # [B, n_shards, pages_per_shard]
    token_shard: jax.Array,  # [B, S] owner shard per token
    token_slot: jax.Array,   # [B, S] table slot per token
    token_off: jax.Array,    # [B, S] in-page offset per token
    token_valid: jax.Array,  # [B, S] bool
    *,
    mesh: Mesh,
    batch_axis,
    combine_axes: Tuple[str, ...],
) -> jax.Array:
    """Anchor a full prompt's KV into pages (ingress path). Each chip writes
    only the pages it owns — no cross-chip payload movement."""
    page_size = pool.shape[1]
    bspec = P(batch_axis)
    pool_spec = P(tuple(mesh.axis_names))

    def local(k, v, pool, tables, tsh, tsl, toff, tval):
        midx = _combined_axis_index(combine_axes)
        b, s, hkv, hd = k.shape
        pid = jnp.take_along_axis(
            tables[jnp.arange(b)[:, None], tsh], tsl[..., None], axis=2
        )[..., 0]                                              # [B, S]
        mine = (tsh == midx) & tval & (pid >= 0)
        write_pid = jnp.where(mine, pid, pool.shape[0])
        kv = jnp.stack([k, v], axis=2).astype(pool.dtype)      # [B, S, 2, Hkv, hd]
        pool = pool.at[write_pid.reshape(-1), toff.reshape(-1)].set(
            kv.reshape(b * s, 2, hkv, hd), mode="drop")
        return pool

    shard = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(bspec, bspec, pool_spec, bspec, bspec, bspec, bspec, bspec),
        out_specs=pool_spec,
        check_vma=False,
    )
    return shard(k, v, pool, block_tables, token_shard, token_slot, token_off,
                 token_valid)
