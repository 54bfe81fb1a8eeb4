"""Shared helpers for the selective-copy kernel gates.

Used by both tests/test_kernels.py and scripts/check_kernel_parity.py so
the regression test and the CI gate assert the SAME property with the same
machinery (case shapes, and the jaxpr walk that proves the reserved-scratch
hot path performs no pool-sized copy).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

# single source of truth for the zero-copy trace invariants lives in the
# analysis suite; re-exported here for the existing test/gate imports
from repro.analysis.jaxpr_audit import (  # noqa: F401
    POOL_COPY_PRIMS,
    jaxpr_primitives,
)


def selcopy_case(rng: np.random.Generator, b: int = 2, page: int = 8,
                 pps: int = 4, meta_max: int = 16) -> Tuple:
    """(stream, meta_len, total_len, pool_with_scratch, tables) with random
    parse boundaries; the pool's LAST row is the reserved scratch page
    (slice it off for legacy-mode calls). The stream is random in every
    lane: metadata prefix in ``[0, meta_len)``, payload from lane
    ``meta_max`` on, as the kernels' layout places them."""
    s = meta_max + pps * page
    p_total = b * pps + 2
    stream = jnp.array(rng.integers(1, 1000, (b, s)), jnp.int32)
    meta_len, total_len = [], []
    tables = np.full((b, pps), -1, np.int32)
    ctr = 0
    for i in range(b):
        ml = int(rng.integers(0, meta_max + 1))
        pl = int(rng.integers(0, pps * page + 1))
        meta_len.append(ml)
        total_len.append(ml + pl)
        for j in range(-(-pl // page)):
            tables[i, j] = ctr
            ctr += 1
    pool = jnp.array(rng.integers(0, 5, (p_total + 1, page)), jnp.int32)
    return (stream, jnp.array(meta_len, jnp.int32),
            jnp.array(total_len, jnp.int32), pool, jnp.array(tables))


def selcopy_crypto_case(rng: np.random.Generator, b: int = 2, page: int = 8,
                        pps: int = 4, meta_max: int = 16) -> Tuple:
    """A :func:`selcopy_case` plus a [B, S] int32 keystream operand — 31-bit
    values on the payload lanes (the kTLS-analogue hw mode), zero elsewhere,
    exactly as the batched datapath builds it."""
    stream, ml, tl, pool, tables = selcopy_case(rng, b=b, page=page, pps=pps,
                                                meta_max=meta_max)
    s = stream.shape[1]
    ks = rng.integers(0, 1 << 31, (b, s)).astype(np.int32)
    rel = np.arange(s)[None, :] - meta_max                 # payload-relative
    payload_lane = (rel >= 0) & (rel < (np.array(tl) - np.array(ml))[:, None])
    ks = np.where(payload_lane, ks, 0).astype(np.int32)
    return stream, ml, tl, pool, tables, jnp.array(ks)


def selgather_case(rng: np.random.Generator, b: int = 2, page: int = 8,
                   pps: int = 4, p_total: int = 0) -> Tuple:
    """(pool_with_scratch, tables, lengths, keystream) for the egress
    gather kernel: random page contents, random per-row lengths in
    [0, pps*page], valid table prefixes, a payload-relative 31-bit
    keystream zeroed past each length (exactly as forward_batch builds
    it)."""
    p_total = p_total or b * pps + 2
    pool = jnp.array(rng.integers(1, 1000, (p_total + 1, page)), jnp.int32)
    tables = np.full((b, pps), -1, np.int32)
    lengths = []
    ctr = 0
    for i in range(b):
        ln = int(rng.integers(0, pps * page + 1))
        lengths.append(ln)
        for j in range(-(-ln // page)):
            tables[i, j] = ctr % p_total
            ctr += 1
    ks = rng.integers(0, 1 << 31, (b, pps * page)).astype(np.int32)
    pos = np.arange(pps * page)[None, :]
    ks = np.where(pos < np.array(lengths)[:, None], ks, 0).astype(np.int32)
    return (pool, jnp.array(tables), jnp.array(lengths, jnp.int32),
            jnp.array(ks))


def policy_case(rng: np.random.Generator, b: int = 4, meta_max: int = 16,
                r: int = 6, k: int = 3) -> Tuple:
    """(meta, meta_len, cond_off, cond_lo, cond_hi, keystream) for the
    policy-match kernel: random metadata rows with random valid lengths, a
    dense [R, K] condition table mixing padding slots (-1), in-range and
    out-of-range offsets, and narrow/wide value bands (so matches, misses
    and no-match sentinels all occur), plus a [B, M] 31-bit keystream
    zeroed past each row's metadata (the hw-kTLS operand: the kernel
    matches meta XOR keystream)."""
    meta = rng.integers(0, 200, (b, meta_max)).astype(np.int32)
    meta_len = rng.integers(1, meta_max + 1, b).astype(np.int32)
    cond_off = rng.integers(-1, meta_max + 3, (r, k)).astype(np.int32)
    lo = rng.integers(0, 200, (r, k)).astype(np.int32)
    width = rng.integers(0, 120, (r, k)).astype(np.int32)
    ks = rng.integers(0, 1 << 31, (b, meta_max)).astype(np.int32)
    pos = np.arange(meta_max)[None, :]
    ks = np.where(pos < meta_len[:, None], ks, 0).astype(np.int32)
    return (jnp.array(meta), jnp.array(meta_len), jnp.array(cond_off),
            jnp.array(lo), jnp.array((lo + width).astype(np.int32)),
            jnp.array(ks))


def policy_live_column(rng: np.random.Generator, r: int) -> jnp.ndarray:
    """A random [R] int32 backend-health rule mask for the policy-match
    kernel's ``live`` operand: mostly-live rows with a sprinkling of dead
    ones (the HealthTable shape under partial backend failure), never
    all-dead so first-match and no-match sentinels both still occur."""
    live = (rng.random(r) < 0.7).astype(np.int32)
    if not live.any():
        live[int(rng.integers(0, r))] = 1
    return jnp.array(live)


def policy_payload_case(rng: np.random.Generator, b: int = 4,
                        meta_max: int = 16, r: int = 6, k: int = 3,
                        w: int = 8) -> Tuple:
    """A :func:`policy_case` where ~a third of the conditions are remapped
    to *payload-prefix* slots (``offset <= -2`` encodes first-anchored-page
    position ``-offset - 2``, in-window and past-window positions both
    drawn), plus the [B, W] plaintext first-page window and the [B]
    payload lengths the match gates on. Returns (meta, meta_len, cond_off,
    cond_lo, cond_hi, keystream, payload, payload_len)."""
    meta, ml, off, lo, hi, ks = policy_case(rng, b=b, meta_max=meta_max,
                                            r=r, k=k)
    off = np.array(off)
    sel = rng.random((r, k)) < 0.35
    ppos = rng.integers(0, w + 3, (r, k))
    off = np.where(sel, -2 - ppos, off).astype(np.int32)
    payload = rng.integers(0, 200, (b, w)).astype(np.int32)
    payload_len = rng.integers(0, w + 1, b).astype(np.int32)
    return (meta, ml, jnp.array(off), lo, hi, ks,
            jnp.array(payload), jnp.array(payload_len))


def fused_round_case(rng: np.random.Generator, b: int = 2, page: int = 8,
                     pps: int = 4, meta_max: int = 16, r: int = 6,
                     k: int = 3) -> dict:
    """Full operand bundle for the one-kernel fused round: a crypto
    selective-copy case plus a payload-relative TX keystream (zeroed past
    each payload length), a policy table mixing metadata / padding /
    payload-prefix conditions, a live health column, and a
    standalone-contract metadata keystream. Preserves the fused-round
    caller invariant ``S = meta_max + pps*page``.
    Returned as a dict keyed by :func:`repro.kernels.ops.fused_round`
    argument names (drop keys to exercise the optional-operand matrix)."""
    stream, ml, tl, pool, tables, ks = selcopy_crypto_case(
        rng, b=b, page=page, pps=pps, meta_max=meta_max)
    mlv, tlv = np.array(ml), np.array(tl)
    plen = tlv - mlv
    tx = rng.integers(0, 1 << 31, (b, pps * page)).astype(np.int32)
    pos = np.arange(pps * page)[None, :]
    tx = np.where(pos < plen[:, None], tx, 0).astype(np.int32)
    cond_off = rng.integers(-1, meta_max + 3, (r, k)).astype(np.int32)
    pay = rng.random((r, k)) < 0.3
    ppos = rng.integers(0, page + 3, (r, k))
    cond_off = np.where(pay, -2 - ppos, cond_off).astype(np.int32)
    lo = rng.integers(0, 1200, (r, k)).astype(np.int32)
    width = rng.integers(0, 800, (r, k)).astype(np.int32)
    mks = rng.integers(0, 1 << 31, (b, meta_max)).astype(np.int32)
    mks = np.where(np.arange(meta_max)[None, :] < mlv[:, None], mks, 0)
    return dict(stream=stream, meta_len=ml, total_len=tl, pool=pool,
                tables=tables, keystream=ks, tx_keystream=jnp.array(tx),
                cond_off=jnp.array(cond_off), cond_lo=jnp.array(lo),
                cond_hi=jnp.array((lo + width).astype(np.int32)),
                live=policy_live_column(rng, r),
                meta_ks=jnp.array(mks.astype(np.int32)))


