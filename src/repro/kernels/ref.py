"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function defines the exact semantics its kernel must reproduce; tests
sweep shapes/dtypes and assert allclose between kernel (interpret=True on
CPU) and these references.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(
    q: jax.Array,   # [B, Hq, Sq, hd]
    k: jax.Array,   # [B, Hkv, Skv, hd]
    v: jax.Array,   # [B, Hkv, Skv, hd]
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    b, hq, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    iq = jnp.arange(sq)[:, None]
    ik = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok = ok & (iq >= ik)
    if window > 0:
        ok = ok & (iq - ik < window)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, hd).astype(q.dtype)


def paged_attention_ref(
    q: jax.Array,        # [B, Hq, hd]
    pool: jax.Array,     # [P, page, 2, Hkv, hd]
    tables: jax.Array,   # [B, pps] local page ids, -1 invalid
    page_pos: jax.Array, # [B, pps] base position per page
    seq_lens: jax.Array, # [B] highest valid position (inclusive)
    *,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-softmax attention over owned pages. Returns (acc [B,Hq,hd],
    m [B,Hq], l [B,Hq]) — partial stats, combinable across shards."""
    b, hq, hd = q.shape
    p_, page, _, hkv, _ = pool.shape
    pps = tables.shape[1]
    g = hq // hkv
    pages = pool[jnp.clip(tables, 0)]                    # [B, pps, page, 2, Hkv, hd]
    kk = pages[:, :, :, 0].reshape(b, pps * page, hkv, hd)
    vv = pages[:, :, :, 1].reshape(b, pps * page, hkv, hd)
    pos = page_pos[:, :, None] + jnp.arange(page)[None, None, :]
    valid = (tables[:, :, None] >= 0) & (pos <= seq_lens[:, None, None])
    if window > 0:
        valid = valid & (seq_lens[:, None, None] - pos < window)
    valid = valid.reshape(b, pps * page)
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, kk.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgt,bthd->bhgd", p, vv.astype(jnp.float32))
    return (acc.reshape(b, hq, hd), m.reshape(b, hq), l.reshape(b, hq))


def _flat(x):
    """[B, W] view of a [B, W] operand or of its row view [B, 1, W]."""
    return x.reshape(x.shape[0], x.shape[-1])


def _anchor_ref(stream, meta_len, total_len, pool, tables, payload, *,
                meta_max):
    """Shared body of the selective-copy oracles: ``stream`` supplies the
    metadata window, ``payload`` (same [B, S] layout; plaintext) the
    tokens that land in the pages."""
    stream, payload = _flat(stream), _flat(payload)
    b, s = stream.shape
    page = pool.shape[-1]
    flat_pool = pool.reshape(-1, page)
    p_ = flat_pool.shape[0]
    pps = tables.shape[1]
    idx = jnp.arange(meta_max)
    meta_buf = jnp.where(idx[None, :] < meta_len[:, None],
                         jnp.take_along_axis(
                             stream, jnp.minimum(idx[None, :], s - 1), axis=1),
                         0)
    # payload token t (stream lane meta_max + t) -> page t // page
    t = jnp.arange(s)
    rel = t[None, :] - meta_max                            # payload-relative
    valid = (rel >= 0) & (rel < (total_len - meta_len)[:, None])
    pg = jnp.clip(rel // page, 0, pps - 1)
    dest_page = jnp.take_along_axis(tables, pg, axis=1)   # [B, S]
    dest_off = rel % page
    flat_dest = jnp.where(valid & (dest_page >= 0),
                          dest_page * page + dest_off, p_ * page)
    new_pool = flat_pool.reshape(-1).at[flat_dest.reshape(-1)].set(
        payload.reshape(-1).astype(pool.dtype), mode="drop")
    return meta_buf, new_pool.reshape(pool.shape)


def selective_copy_ref(
    stream: jax.Array,    # [B, S] int32 token stream (or [B, 1, S])
    meta_len: jax.Array,  # [B] metadata boundary from the parser policy
    total_len: jax.Array, # [B] message length (metadata + payload)
    pool: jax.Array,      # [P, page] anchored payload pages (or [P, 1, page])
    tables: jax.Array,    # [B, pps] destination page ids (-1 unused)
    *,
    meta_max: int,
) -> Tuple[jax.Array, jax.Array]:
    """RX-Prog data plane: compact metadata into [B, meta_max] (selective
    copy) and scatter the payload into anchored pages (single placement).
    The stream holds the metadata in lanes ``[0, meta_len)`` and the
    payload from lane ``meta_max`` on. The pool may be given as its row
    view ``[P, 1, page]``; the new pool comes back in the same shape.
    Returns (meta_buf, new_pool)."""
    return _anchor_ref(stream, meta_len, total_len, pool, tables, stream,
                       meta_max=meta_max)


def selective_copy_crypto_ref(
    stream: jax.Array,    # [B, S] int32 ciphertext stream (or [B, 1, S])
    meta_len: jax.Array,  # [B] metadata boundary from the parser policy
    total_len: jax.Array, # [B] message length (metadata + payload)
    pool: jax.Array,      # [P, page] anchored payload pages (or [P, 1, page])
    tables: jax.Array,    # [B, pps] destination page ids (-1 unused)
    keystream: jax.Array, # stream-shaped keystream (0 outside payload)
    *,
    meta_max: int,
) -> Tuple[jax.Array, jax.Array]:
    """hw-kTLS RX-Prog data plane: identical to :func:`selective_copy_ref`
    except payload tokens are XORed with ``keystream`` *inside* the
    anchoring scatter — the NIC-inline decrypt, fused into the single
    placement pass. The metadata compaction stays raw (record headers are
    plaintext; inner-metadata decryption happens host-side during the user
    copy, where the bytes are being touched anyway)."""
    stream = _flat(stream)
    plain = jnp.bitwise_xor(stream, _flat(keystream).astype(stream.dtype))
    return _anchor_ref(stream, meta_len, total_len, pool, tables, plain,
                       meta_max=meta_max)


def selective_gather_ref(
    pool: jax.Array,      # [P+1, page] pages + scratch row (or [P+1, 1, page])
    tables: jax.Array,    # [B, pps] source page ids (-1 unused)
    lengths: jax.Array,   # [B] payload lengths
    keystream: Optional[jax.Array] = None,  # [B, pps*page] or None
) -> jax.Array:
    """TX-Prog data plane: gather each message's anchored payload out of
    the pool in one pass. ``out[i, :lengths[i]]`` holds the payload (page
    ``tables[i, j]`` supplies positions ``[j*page, (j+1)*page)``); lanes
    past the length — and lanes of invalid (-1) table slots — are zero.
    ``keystream`` (payload-relative) is XORed into the gathered tokens
    inside the same pass (hw-kTLS NIC-inline TX encrypt)."""
    page = pool.shape[-1]
    b, pps = tables.shape
    out = pool.reshape(-1, page)[jnp.clip(tables, 0)].reshape(b, pps * page)
    pos = jnp.arange(pps * page)
    valid = (jnp.repeat(tables >= 0, page, axis=1)
             & (pos[None, :] < lengths[:, None]))
    if keystream is not None:
        out = jnp.bitwise_xor(out, _flat(keystream).astype(out.dtype))
    return jnp.where(valid, out, 0)


def policy_match_ref(
    meta: jax.Array,       # [B, M] int32 metadata tokens (round-padded)
    meta_len: jax.Array,   # [B] int32 valid metadata lengths
    cond_off: jax.Array,   # [R, K] int32 offsets (-1 pad; <= -2 payload)
    cond_lo: jax.Array,    # [R, K] int32 inclusive lower bounds
    cond_hi: jax.Array,    # [R, K] int32 inclusive upper bounds
    keystream: Optional[jax.Array] = None,   # [B, M] int32 or None
    live: Optional[jax.Array] = None,        # [R] int32 health mask or None
    payload: Optional[jax.Array] = None,     # [B, W] first-page window
    payload_len: Optional[jax.Array] = None, # [B] payload lengths
) -> jax.Array:
    """L7 policy table first-match pass (the in-data-plane routing
    decision). A condition holds iff its offset is the padding slot
    (``-1``), or ``0 <= offset < meta_len`` and ``lo <= meta[offset] <=
    hi``, or — *payload-prefix* conditions, ``offset <= -2`` encoding
    first-anchored-page position ``-offset - 2`` — the position is inside
    both the window and the payload and the window token is in bounds.
    A rule matches iff all K conditions hold; the result is the FIRST
    matching rule per message (rule order is priority), ``R`` when none
    match. ``keystream`` (0 on plaintext lanes) is XORed in before
    matching — the hw-kTLS analogue matches against *decrypted* metadata
    without a separate decrypt pass. ``live`` (the backend-health rule
    mask; 0 = every backend of the rule is down) excludes dead rules from
    the first-match scan so priority falls through in-plane. ``payload``
    is the [B, W] *plaintext* window of each message's first anchored
    page; when omitted, payload-prefix conditions never hold. Returns [B]
    int32 rule indices."""
    b, mm = meta.shape
    r, k = cond_off.shape
    m = meta if keystream is None else jnp.bitwise_xor(
        meta, keystream.astype(meta.dtype))
    vals = m[:, jnp.clip(cond_off, 0, mm - 1)]               # [B, R, K]
    pad = cond_off == -1                                      # [R, K]
    present = (cond_off >= 0)[None] \
        & (cond_off[None] < meta_len[:, None, None]) \
        & (cond_off[None] < mm)
    ok = pad[None] | (present & (vals >= cond_lo[None])
                      & (vals <= cond_hi[None]))
    if payload is not None:
        w = payload.shape[1]
        ppos = -cond_off - 2                                  # [R, K]
        pvals = payload[:, jnp.clip(ppos, 0, w - 1)]          # [B, R, K]
        pay_ok = (cond_off <= -2)[None] \
            & (ppos[None] < payload_len[:, None, None]) & (ppos < w)[None] \
            & (pvals >= cond_lo[None]) & (pvals <= cond_hi[None])
        ok = ok | pay_ok
    rule_ok = ok.all(axis=2)                                  # [B, R]
    if live is not None:
        rule_ok &= live.reshape(1, r) > 0
    ridx = jnp.arange(r, dtype=jnp.int32)
    return jnp.min(jnp.where(rule_ok, ridx[None, :], r),
                   axis=1).astype(jnp.int32)


def fused_round_ref(
    stream: jax.Array,     # [B, S] int32 token stream
    meta_len: jax.Array,   # [B] int32
    total_len: jax.Array,  # [B] int32
    pool: jax.Array,       # [P+1, page] int32 + scratch row (or [P+1, 1, page])
    tables: jax.Array,     # [B, pps] int32 page ids (-1 unused)
    *,
    meta_max: int,
    keystream: Optional[jax.Array] = None,      # [B, S] hw-kTLS RX
    tx_keystream: Optional[jax.Array] = None,   # [B, pps*page] hw-kTLS TX
    cond_off: Optional[jax.Array] = None,       # [R, K] policy table
    cond_lo: Optional[jax.Array] = None,
    cond_hi: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,           # [R] health column
    meta_ks: Optional[jax.Array] = None,        # [B, meta_max] meta keystream
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array], jax.Array]:
    """One-kernel scheduling round oracle: selective copy + hw-kTLS RX
    decrypt + policy first-match (with payload-prefix conditions peeking
    the first anchored page) + egress gather, composed from the per-pass
    references. Returns ``(meta [B, meta_max], new_pool, verdict [B] |
    None, out [B, pps*page])`` — the exact semantics
    ``selective_copy.fused_round`` must reproduce. The stream and
    keystreams may be given as their row views ``[B, 1, W]``."""
    stream = _flat(stream)
    if keystream is None:
        meta, new_pool = selective_copy_ref(
            stream, meta_len, total_len, pool, tables, meta_max=meta_max)
        plain = stream
    else:
        meta, new_pool = selective_copy_crypto_ref(
            stream, meta_len, total_len, pool, tables, keystream,
            meta_max=meta_max)
        plain = jnp.bitwise_xor(stream, _flat(keystream).astype(stream.dtype))
    plen = total_len - meta_len
    verdict = None
    if cond_off is not None:
        # first-anchored-page window: payload-relative positions [0, page)
        # (lanes past the payload are gated off by the ppos < payload_len
        # check inside the match)
        window = plain[:, meta_max : meta_max + pool.shape[-1]]
        mrow = meta if meta_ks is None else jnp.bitwise_xor(
            meta, meta_ks.astype(meta.dtype))
        verdict = policy_match_ref(mrow, meta_len, cond_off, cond_lo, cond_hi,
                                   None, live, payload=window,
                                   payload_len=plen)
    out = selective_gather_ref(new_pool, tables, plen, tx_keystream)
    return meta, new_pool, verdict, out


def mlstm_scan_ref(q, k, v, log_i, log_f):
    """Sequential mLSTM oracle. q/k/v [B, H, S, dh]; gates [B, H, S].
    Returns h [B, H, S, dh]."""
    from repro.models.ssm import mlstm_cell_sequential

    h, _ = mlstm_cell_sequential(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), log_i.transpose(0, 2, 1),
        log_f.transpose(0, 2, 1))
    return h.transpose(0, 2, 1, 3)
