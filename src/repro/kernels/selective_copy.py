"""Selective-copy ingress + gather egress Pallas TPU kernels (the RX-Prog
and TX-Prog data planes).

One **fused** kernel performs both halves of the paper's ingress action in a
single pass over the stream:

  * **selective copy** — the metadata prefix (boundary supplied by the
    parser policy, scalar-prefetched) is compacted into a small [B, M]
    buffer (the only bytes that cross to the control plane);
  * **payload anchoring** — payload tokens are placed page-by-page into the
    anchored pool, addressed through the block table. The destination page
    index is known before the DMA issues (SMEM metadata), so the payload is
    written exactly once and never touched again.

The grid is flattened to ``(B, 1 + pps)``: step ``j == 0`` of each row
writes the metadata block, steps ``j >= 1`` anchor payload page ``j - 1``.
The stream block index depends only on ``b``, so each row is fetched into
VMEM once and shared by its metadata and payload steps.

Pool updates are in-place via input_output_aliasing (the anchored payload
is donated, like the kernel socket buffer it models). Invalid table
entries (-1) and the metadata step are routed to a *scratch page row*;
with ``reserved_scratch=True`` that row is the one :class:`AnchorPool`
reserves inside the pool at allocation time, so the hot path performs **no
pool-sized copy at all** (no ``concatenate``; the donation stays a true
in-place update). The legacy mode (``reserved_scratch=False``) appends a
dummy row per call for callers that still hold a scratch-less pool.

**kTLS-analogue hw mode**: an optional ``keystream`` operand (same [B, S]
layout as the stream) is XORed into the payload tokens *inside* the
anchoring step — the NIC-inline decrypt, fused into the same single pass
(paper §B.1: hardware kTLS adds zero extra passes). The metadata step
stays raw: record headers are plaintext and inner-metadata decryption
happens host-side during the user copy. Plaintext calls (``keystream
None``) compile exactly the pre-crypto kernel — no extra operand, no
extra VMEM traffic. Matches ``kernels.ref.selective_copy_crypto_ref``.

:func:`selective_gather` is the egress mirror: one fused pass reads each
message's anchored pages back out of the **resident** pool (read-only, no
donation, no pool-sized copy) into a dense [B, pps*page] payload block,
with the same optional ``keystream`` operand fusing the hw-kTLS TX encrypt
into the gather — together the two kernels close the batched datapath loop
entirely on-device.

Layout: stream [B, S] int32 with the metadata prefix in lanes ``[0,
meta_len)`` and the payload from lane ``meta_max`` on (``S >= meta_max +
pps*page``); pool [P(+1), page] int32; tables [B, pps]. Inside the kernels
every [N, W] operand is viewed as [N, 1, W], so a one-row block spans the
two tiled dims whole. The pool, the stream and the keystreams may also be
passed already in that row view: on a TPU a [N, W] array is tiled (8, 128)
and its row view (1, 128), so reshaping on the device is a relayout copy,
while the host builds the row view for free (the resident
:class:`~repro.core.device_pool.DevicePool` keeps its pool so, and
``LibraStack`` builds its round operands so). A row-view pool comes back
in the same shape. Compiled (non-interpret) calls need ``page`` and
``meta_max`` to be multiples of :data:`LANE`, so every payload window
starts on a lane tile; the host builds ``meta_max`` with
:func:`stream_meta_width`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes in one 32-bit TPU vector tile: compiled kernels slice the stream
#: and move pages only at multiples of it
LANE = 128


def stream_meta_width(meta_len_max: int, page: int) -> int:
    """``meta_max`` for a round whose longest metadata prefix is
    ``meta_len_max``: the metadata region rounded up to whole multiples of
    ``gcd(page, LANE)`` — a lane tile for every page size the compiled
    kernels accept, so the payload behind it starts lane-aligned, and no
    wider than a page's alignment needs elsewhere."""
    align = math.gcd(page, LANE)
    return max(-(-meta_len_max // align), 1) * align


def _check_tiling(interpret: bool, **dims: int) -> None:
    """Compiled kernels move pages and slice the stream at lane-tile
    offsets only; refuse other sizes instead of miscompiling them."""
    bad = {k: v for k, v in dims.items() if v % LANE}
    if bad and not interpret:
        raise ValueError(
            f"compiled selective-copy kernels need {sorted(dims)} to be "
            f"multiples of {LANE}, got {bad}; build the stream with "
            f"stream_meta_width() and a lane-multiple page size, or run "
            f"impl='interpret'/'ref'")


def _rows(x: jax.Array) -> jax.Array:
    """[N, W] -> the row view [N, 1, W] (a no-op on a row view)."""
    return x.reshape(x.shape[0], 1, x.shape[-1])


def _meta_window(window: jax.Array, mlen) -> jax.Array:
    """The [1, M] metadata window with lanes past ``mlen`` zeroed."""
    idx = jax.lax.broadcasted_iota(jnp.int32, window.shape, 1)
    return jnp.where(idx < mlen, window, 0)


def _payload_start(jj, *, meta_max: int, page: int):
    """Stream lane of payload page ``jj`` — a multiple of both sizes'
    common divisor, which the compiled path makes a lane tile."""
    return pl.multiple_of(meta_max + jj * page, math.gcd(meta_max, page))


def _anchor_kernel(mlen_ref, tlen_ref, tables_ref, stream_ref, *rest,
                   page: int, pps: int, meta_max: int, has_ks: bool):
    if has_ks:
        ks_ref, pool_in_ref, meta_ref, pool_ref = rest
    else:
        pool_in_ref, meta_ref, pool_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)   # 0 = metadata step; j >= 1 anchors payload page j-1
    mlen = mlen_ref[b]
    tlen = tlen_ref[b]

    @pl.when(j == 0)
    def _meta():
        meta_ref[...] = _meta_window(stream_ref[:, :meta_max], mlen)

    # payload step: j == 0 is aimed at the scratch row by the index map and
    # must pass the block through untouched (valid is forced False below)
    jj = jnp.maximum(j - 1, 0)
    pid = tables_ref[b * pps + jj]
    start = _payload_start(jj, meta_max=meta_max, page=page)
    toks = stream_ref[:, pl.ds(start, page)]
    if has_ks:
        # hw-kTLS: decrypt on the fly, inside the one placement pass
        toks = jnp.bitwise_xor(toks, ks_ref[:, pl.ds(start, page)])
    rel = jj * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = (j > 0) & (pid >= 0) & (rel + mlen < tlen)
    # always write the block: invalid lanes / skipped pages pass the original
    # page content through (the scratch block is revisited via the routed index)
    pool_ref[...] = jnp.where(valid, toks, pool_in_ref[...])


def _selective_copy_impl(
    stream: jax.Array,    # [B, S] int32 (or its row view [B, 1, S])
    meta_len: jax.Array,  # [B] int32
    total_len: jax.Array, # [B] int32
    pool: jax.Array,      # [P, page] int32 (donated); [P+1, page] w/ scratch
    tables: jax.Array,    # [B, pps] int32
    *,
    meta_max: int,
    interpret: bool = False,
    reserved_scratch: bool = False,
    keystream: jax.Array = None,   # stream-shaped int32 (hw-kTLS) or None
):
    """Returns (meta_buf [B, meta_max], new_pool). Matches
    kernels.ref.selective_copy_ref (selective_copy_crypto_ref when a
    ``keystream`` is supplied).

    With ``reserved_scratch=True`` the pool's LAST row is the scratch page
    reserved by :attr:`AnchorPool.scratch_page` at allocation time: nothing
    is concatenated, the donation is honoured in place, and ``new_pool``
    keeps the full (scratch-inclusive) shape. Table entries must never
    reference the scratch row (the allocator never hands it out).

    Two jitted entry points share this body: :func:`selective_copy` (the
    default; the caller keeps its pool buffer) and
    :func:`selective_copy_donated`, whose outer jit **donates the pool
    argument** — the resident :class:`~repro.core.device_pool.DevicePool`
    uses it so the in-place aliasing inside the ``pallas_call`` composes
    with outer-level donation and device rounds keep ONE pool buffer
    instead of an input + an output copy. Callers of the donated entry
    must not touch their pool array afterwards (XLA deletes it)."""
    b, s = stream.shape[0], stream.shape[-1]      # [B, S] or [B, 1, S]
    page = pool.shape[-1]
    pps = tables.shape[1]
    assert s >= meta_max + pps * page, (s, meta_max, pps, page)
    _check_tiling(interpret, page=page, meta_max=meta_max)
    has_ks = keystream is not None
    if has_ks:
        assert _rows(keystream).shape == (b, 1, s), (keystream.shape, s)

    pool_ext = _rows(pool)
    if not reserved_scratch:
        # legacy callers hold a scratch-less pool: append a dummy row (one
        # pool-sized copy — the batched datapath never takes this branch)
        pool_ext = jnp.concatenate(
            [pool_ext, jnp.zeros((1, 1, page), pool.dtype)], axis=0)
    p_ext = pool_ext.shape[0]
    scratch = p_ext - 1

    def _pool_index(b_, j, ml, tl, tbl):
        # invalid table entries (-1) and the metadata step are routed to the
        # scratch row so no real page is ever revisited by a non-owner step
        pid = tbl[b_ * pps + jnp.maximum(j - 1, 0)]
        return (jnp.where((j == 0) | (pid < 0), scratch, pid), 0, 0)

    stream_spec = pl.BlockSpec((None, 1, s),
                               lambda b_, j, ml, tl, tbl: (b_, 0, 0))
    page_spec = pl.BlockSpec((None, 1, page), _pool_index)
    in_specs = [stream_spec]
    operands = [_rows(stream)]
    if has_ks:
        in_specs.append(stream_spec)        # keystream rides the stream layout
        operands.append(_rows(keystream))
    in_specs.append(page_spec)
    operands.append(pool_ext)

    meta, new_pool = pl.pallas_call(
        functools.partial(_anchor_kernel, page=page, pps=pps,
                          meta_max=meta_max, has_ks=has_ks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, 1 + pps),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, 1, meta_max),
                             lambda b_, j, ml, tl, tbl: (b_, 0, 0)),
                page_spec,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, meta_max), stream.dtype),
            jax.ShapeDtypeStruct(pool_ext.shape, pool.dtype),
        ],
        # pool donated -> in-place anchoring (operand index counts the 3
        # scalar-prefetch args, the stream, and the optional keystream)
        input_output_aliases={(5 if has_ks else 4): 1},
        interpret=interpret,
    )(meta_len, total_len, tables.reshape(-1), *operands)
    if not reserved_scratch:
        new_pool = new_pool[:-1]
    return meta.reshape(b, meta_max), new_pool.reshape(pool.shape)


_JIT_STATICS = ("meta_max", "interpret", "reserved_scratch")

#: default entry — pool buffer NOT donated (safe for callers that reuse it,
#: e.g. parity checks running several impls against one pool)
selective_copy = jax.jit(_selective_copy_impl, static_argnames=_JIT_STATICS)

#: donating entry — the pool argument (index 3) is donated through the
#: outer jit, so the resident device pool is updated truly in place
#: (one live pool buffer across rounds; see DevicePool.anchor_batch_device)
selective_copy_donated = jax.jit(_selective_copy_impl,
                                 static_argnames=_JIT_STATICS,
                                 donate_argnums=(3,))


#: policy condition-offset encoding (shared with repro.core.policy):
#: ``-1`` is a padding slot (always true); ``<= -2`` is a *payload-prefix*
#: condition matching first-anchored-page position ``-offset - 2``
PAD_COND = -1
PAYLOAD_COND_BASE = -2


def _policy_rule_match(row, mlen, off, lo, hi, *, payload=None, plen=None):
    """Shared condition-evaluation body for the standalone policy kernel
    and the fused round. ``row`` is the [1, M] metadata window, ``off`` /
    ``lo`` / ``hi`` the [R, K] table. Each of the K condition columns is
    evaluated for all R rules at once: metadata conditions gather
    ``row[off]`` via a one-hot lane mask (no dynamic indexing);
    payload-prefix conditions (``off <= -2``) gather position ``-off - 2``
    of the [1, W] first anchored page window the same way. Returns the
    [R, 1] rule_ok mask."""
    r, k = off.shape
    m = row.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, m), 1)
    if payload is not None:
        w = payload.shape[1]
        plane = jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
    rule_ok = None
    for c in range(k):
        o, lo_c, hi_c = off[:, c:c + 1], lo[:, c:c + 1], hi[:, c:c + 1]
        vals = jnp.sum(jnp.where(lane == o, row, 0), axis=1, keepdims=True)
        present = (o >= 0) & (o < mlen) & (o < m)
        ok = (o == PAD_COND) | (present & (vals >= lo_c) & (vals <= hi_c))
        if payload is not None:
            # payload-prefix conditions: position -off-2 of the message's
            # first anchored page, gated on the window and the payload length
            ppos = PAYLOAD_COND_BASE - o
            pvals = jnp.sum(jnp.where(plane == ppos, payload, 0), axis=1,
                            keepdims=True)
            ok = ok | ((o <= PAYLOAD_COND_BASE) & (ppos < plen) & (ppos < w)
                       & (pvals >= lo_c) & (pvals <= hi_c))
        rule_ok = ok if rule_ok is None else rule_ok & ok
    return rule_ok                                         # [R, 1]


def _store_verdict(out_ref, rule_ok, live_ref) -> None:
    """First matching rule index (``R`` = no match) over the [R, 1]
    ``rule_ok`` mask, broadcast across the [1, LANE] verdict block."""
    r = rule_ok.shape[0]
    if live_ref is not None:
        # backend-health column: dead rules (every backend down) never
        # win the first-match scan — failover priority in-plane
        rule_ok &= live_ref[...] > 0
    ridx = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    first = jnp.min(jnp.where(rule_ok, ridx, r), axis=0, keepdims=True)
    out_ref[...] = jnp.broadcast_to(first, out_ref.shape)


def _policy_kernel(*refs, has_ks: bool, has_live: bool, has_payload: bool):
    refs = list(refs)
    mlen_ref = refs.pop(0)
    plen_ref = refs.pop(0) if has_payload else None
    meta_ref = refs.pop(0)
    ks_ref = refs.pop(0) if has_ks else None
    off_ref, lo_ref, hi_ref = refs[:3]
    refs = refs[3:]
    live_ref = refs.pop(0) if has_live else None
    payload_ref = refs.pop(0) if has_payload else None
    (out_ref,) = refs
    b = pl.program_id(0)
    row = meta_ref[...]                                    # [1, M]
    if has_ks:
        # hw-kTLS: match against decrypted metadata — the keystream XOR
        # fused into the match pass, no separate decrypt
        row = jnp.bitwise_xor(row, ks_ref[...])
    rule_ok = _policy_rule_match(
        row, mlen_ref[b], off_ref[...], lo_ref[...], hi_ref[...],
        payload=payload_ref[...] if has_payload else None,
        plen=plen_ref[b] if has_payload else None)
    _store_verdict(out_ref, rule_ok, live_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def policy_match(
    meta: jax.Array,       # [B, M] int32 metadata tokens (round-padded)
    meta_len: jax.Array,   # [B] int32
    cond_off: jax.Array,   # [R, K] int32 (-1 = padding; <= -2 payload-prefix)
    cond_lo: jax.Array,    # [R, K] int32
    cond_hi: jax.Array,    # [R, K] int32
    *,
    interpret: bool = False,
    keystream: jax.Array = None,   # [B, M] int32 (hw-kTLS) or None
    live: jax.Array = None,        # [R] int32 backend-health mask or None
    payload: jax.Array = None,     # [B, W] int32 first-page window or None
    payload_len: jax.Array = None, # [B] int32 payload lengths (with payload)
) -> jax.Array:
    """L7 policy-table first-match kernel — the in-data-plane routing
    decision, fused into the batched metadata pass. One grid step per
    message evaluates all R×K dense conditions against that message's
    metadata row in VMEM and writes the first matching rule index (``R``
    = no match). The optional ``keystream`` operand (same [B, M] layout,
    zeros on plaintext lanes) XORs the metadata inside the same step, so
    hw-kTLS rounds match against decrypted metadata with zero extra
    passes. The optional ``live`` operand ([R] int32, the HealthTable
    rule mask) masks dead rules out of the first-match scan — backend
    failover priority resolved in-plane. The optional ``payload`` operand
    ([B, W] plaintext window of each message's first anchored page, with
    ``payload_len``) serves *payload-prefix* conditions (``cond_off <=
    -2`` encodes page position ``-cond_off - 2``); without it those
    conditions simply never match. Touches only [B, M] metadata, the
    [R, K] table, and the page-sized window — never the payload pool — so
    the hot path performs no pool-sized copy by construction (gated in
    check_kernel_parity). Matches ``kernels.ref.policy_match_ref``.
    Returns [B] int32."""
    b, m = meta.shape
    r, k = cond_off.shape
    has_ks = keystream is not None
    if has_ks:
        assert keystream.shape == meta.shape, (keystream.shape, meta.shape)
    has_live = live is not None
    has_payload = payload is not None
    if has_payload:
        assert payload.shape[0] == b and payload_len is not None, \
            (payload.shape, b)

    meta_spec = pl.BlockSpec((None, 1, m), lambda b_, *_: (b_, 0, 0))
    table_spec = pl.BlockSpec((r, k), lambda b_, *_: (0, 0))
    in_specs = [meta_spec]
    operands = [_rows(meta)]
    if has_ks:
        in_specs.append(meta_spec)       # keystream rides the meta layout
        operands.append(_rows(keystream))
    in_specs += [table_spec, table_spec, table_spec]
    operands += [cond_off, cond_lo, cond_hi]
    if has_live:
        assert live.shape == (r,), (live.shape, r)
        in_specs.append(pl.BlockSpec((r, 1), lambda b_, *_: (0, 0)))
        operands.append(jnp.asarray(live, jnp.int32).reshape(r, 1))
    if has_payload:
        w = payload.shape[1]
        in_specs.append(pl.BlockSpec((None, 1, w), lambda b_, *_: (b_, 0, 0)))
        operands.append(_rows(payload))
        prefetch = (meta_len, jnp.asarray(payload_len, jnp.int32))
    else:
        prefetch = (meta_len,)

    out = pl.pallas_call(
        functools.partial(_policy_kernel, has_ks=has_ks, has_live=has_live,
                          has_payload=has_payload),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, 1, LANE),
                                   lambda b_, *_: (b_, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, LANE), jnp.int32),
        interpret=interpret,
    )(*prefetch, *operands)
    return out[:, 0, 0]


def _gather_kernel(len_ref, tables_ref, pool_ref, *rest,
                   page: int, pps: int, has_ks: bool):
    if has_ks:
        ks_ref, out_ref = rest
    else:
        (out_ref,) = rest
    b = pl.program_id(0)
    j = pl.program_id(1)   # output page slot j covers payload [j*page, ...)
    pid = tables_ref[b * pps + j]
    rel = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = (pid >= 0) & (rel < len_ref[b])
    toks = pool_ref[...]
    if has_ks:
        # hw-kTLS TX: encrypt inline while consuming the anchored page —
        # the same fused single pass as the ingress decrypt
        toks = jnp.bitwise_xor(toks, ks_ref[...])
    out_ref[...] = jnp.where(valid, toks, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_gather(
    pool: jax.Array,      # [P+1, page] int32; last row = reserved scratch
    tables: jax.Array,    # [B, pps] int32 source page ids (-1 unused)
    lengths: jax.Array,   # [B] int32 payload lengths
    *,
    interpret: bool = False,
    keystream: jax.Array = None,   # [B, pps*page] int32 (hw-kTLS TX) or None
):
    """Egress half of the paper's data plane: gather each message's anchored
    payload out of the resident pool in one fused pass — the TX-Prog mirror
    of :func:`selective_copy`'s payload anchoring. Returns ``out [B,
    pps*page]`` where ``out[i, :lengths[i]]`` is message ``i``'s payload
    (page ``tables[i, j]`` supplies payload positions ``[j*page, (j+1)*
    page)``) and every lane past the length is zero. The pool is read-only
    (nothing is donated); invalid table entries (-1) are routed to the
    reserved scratch row and masked, so no real page is ever touched by a
    non-owner step and the call performs **no pool-sized copy**.

    ``keystream`` (payload-relative, zeros past each length) is XORed into
    the gathered tokens inside the same pass — NIC-inline TX encryption,
    zero extra passes. Matches ``kernels.ref.selective_gather_ref``."""
    p_ext, page = pool.shape[0], pool.shape[-1]
    b, pps = tables.shape
    _check_tiling(interpret, page=page)
    scratch = p_ext - 1
    has_ks = keystream is not None
    if has_ks:
        assert _rows(keystream).shape == (b, 1, pps * page), \
            (keystream.shape, (b, pps * page))

    def _pool_index(b_, j, ln, tbl):
        pid = tbl[b_ * pps + j]
        return (jnp.where(pid < 0, scratch, pid), 0, 0)

    slot_spec = pl.BlockSpec((None, 1, page), lambda b_, j, ln, tbl: (b_, 0, j))
    in_specs = [pl.BlockSpec((None, 1, page), _pool_index)]
    operands = [_rows(pool)]
    if has_ks:
        in_specs.append(slot_spec)
        operands.append(_rows(keystream))

    out = pl.pallas_call(
        functools.partial(_gather_kernel, page=page, pps=pps, has_ks=has_ks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pps),
            in_specs=in_specs,
            out_specs=slot_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, pps * page), pool.dtype),
        interpret=interpret,
    )(lengths, tables.reshape(-1), *operands)
    return out.reshape(b, pps * page)


def _fused_round_kernel(mlen_ref, tlen_ref, tables_ref, *refs,
                        page: int, pps: int, meta_max: int, b_rows: int,
                        has_ks: bool, has_txks: bool, has_policy: bool,
                        has_live: bool, has_meta_ks: bool, n_buffers: int):
    refs = list(refs)
    stream_ref = refs.pop(0)
    ks_ref = refs.pop(0) if has_ks else None
    txks_ref = refs.pop(0) if has_txks else None
    pool_in_ref = refs.pop(0)
    off_ref = lo_ref = hi_ref = metaks_ref = live_ref = None
    if has_policy:
        off_ref, lo_ref, hi_ref = refs[:3]
        refs = refs[3:]
        metaks_ref = refs.pop(0) if has_meta_ks else None
        live_ref = refs.pop(0) if has_live else None
    meta_ref = refs.pop(0)
    pool_ref = refs.pop(0)
    out_ref = refs.pop(0)
    verdict_ref = refs.pop(0) if has_policy else None
    stream_buf = stream_sem = ks_buf = ks_sem = None
    if n_buffers:
        stream_buf, stream_sem = refs.pop(0), refs.pop(0)
        if has_ks:
            ks_buf, ks_sem = refs.pop(0), refs.pop(0)
    assert not refs, refs

    b = pl.program_id(0)
    j = pl.program_id(1)   # 0 = metadata+policy step; j >= 1 = payload page j-1
    mlen = mlen_ref[b]
    tlen = tlen_ref[b]

    if n_buffers:
        # row-level DMA staging: the [1, S] stream row (and its keystream
        # row) for batch row b + D - 1 is prefetched from off-chip memory
        # into VMEM slot (b + D - 1) % D while row b computes — metadata
        # prefetch for tile i+1 overlaps compute on tile i. Slot reuse is
        # safe under the sequential grid: row b + D - 1 lands in slot
        # (b - 1) % D, whose previous owner (row b - 1) ran its last grid
        # step before (b, 0) executes.
        def _copies(row):
            slot = row % n_buffers
            yield pltpu.make_async_copy(stream_ref.at[row],
                                        stream_buf.at[slot],
                                        stream_sem.at[slot])
            if has_ks:
                yield pltpu.make_async_copy(ks_ref.at[row], ks_buf.at[slot],
                                            ks_sem.at[slot])

        @pl.when(j == 0)
        def _dma():
            @pl.when(b == 0)
            def _warm_up():
                for i in range(min(n_buffers - 1, b_rows)):
                    for cp in _copies(i):
                        cp.start()

            nxt = b + n_buffers - 1

            @pl.when(nxt < b_rows)
            def _prefetch_ahead():
                for cp in _copies(nxt):
                    cp.start()

            for cp in _copies(b):
                cp.wait()

    def _load_row(start, width, ks=False):
        # one row window [start, start+width) of the stream (or keystream):
        # from this row's VMEM staging slot when DMA-pipelined, else from
        # the blocked operand directly
        if n_buffers:
            buf = ks_buf if ks else stream_buf
            return buf[b % n_buffers, :, pl.ds(start, width)]
        ref = ks_ref if ks else stream_ref
        return ref[:, pl.ds(start, width)]

    # ---- anchor + egress gather (j >= 1; j == 0 routed to scratch) ----
    jj = jnp.maximum(j - 1, 0)
    pid = tables_ref[b * pps + jj]
    start = _payload_start(jj, meta_max=meta_max, page=page)
    toks = _load_row(start, page)
    if has_ks:
        # hw-kTLS RX: decrypt on the fly, inside the one placement pass
        toks = jnp.bitwise_xor(toks, _load_row(start, page, ks=True))
    rel = jj * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = (j > 0) & (pid >= 0) & (rel + mlen < tlen)
    pool_ref[...] = jnp.where(valid, toks, pool_in_ref[...])

    @pl.when(j > 0)
    def _gather():
        # egress half fused in: the freshly anchored tokens are still in
        # registers, so the gather re-reads nothing from the pool. Anchor
        # validity (rel + mlen < tlen) IS gather validity (rel < plen).
        gtoks = toks
        if has_txks:
            # speculative hw-kTLS TX encrypt for the hinted destination
            gtoks = jnp.bitwise_xor(gtoks, txks_ref[...])
        out_ref[...] = jnp.where(valid, gtoks, 0)

    @pl.when(j == 0)
    def _meta():
        window = _meta_window(_load_row(0, meta_max), mlen)
        meta_ref[...] = window
        if has_policy:
            row = window
            if has_meta_ks:
                row = jnp.bitwise_xor(row, metaks_ref[...])
            # payload-prefix window: the first anchored page, decrypted
            # with the same rx-keystream lanes the anchoring step consumes
            # (lanes past the payload are gated off by ppos < plen)
            prow = _load_row(meta_max, page)
            if has_ks:
                prow = jnp.bitwise_xor(prow, _load_row(meta_max, page,
                                                       ks=True))
            rule_ok = _policy_rule_match(
                row, mlen, off_ref[...], lo_ref[...], hi_ref[...],
                payload=prow, plen=tlen - mlen)
            _store_verdict(verdict_ref, rule_ok, live_ref)


def _fused_round_impl(
    stream: jax.Array,     # [B, S] int32 (or its row view [B, 1, S])
    meta_len: jax.Array,   # [B] int32
    total_len: jax.Array,  # [B] int32
    pool: jax.Array,       # [P+1, page] int32; last row = reserved scratch
    tables: jax.Array,     # [B, pps] int32
    keystream: jax.Array = None,      # stream-shaped hw-kTLS RX or None
    tx_keystream: jax.Array = None,   # [B, pps*page] int32 hw-kTLS TX or None
    cond_off: jax.Array = None,       # [R, K] int32 policy table or None
    cond_lo: jax.Array = None,
    cond_hi: jax.Array = None,
    live: jax.Array = None,           # [R] int32 health column or None
    meta_ks: jax.Array = None,        # [B, meta_max] int32 meta ks or None
    *,
    meta_max: int,
    interpret: bool = False,
    n_buffers: int = 0,
):
    """The **one-kernel scheduling round**: a single ``pallas_call`` chains
    selective-copy anchoring, the hw-kTLS keystream XOR, the policy-table
    first-match pass (live health column + payload-prefix conditions
    included), and the egress gather — one launch per round instead of
    three, against the resident pool. Returns ``(meta [B, meta_max],
    new_pool, verdict [B] | None, out [B, pps*page])``; matches
    ``kernels.ref.fused_round_ref`` bit-for-bit.

    Grid ``(B, 1 + pps)``: step ``j == 0`` of each row compacts metadata
    AND produces the policy verdict (the first-page window is loaded once
    and shared); steps ``j >= 1`` anchor payload page ``j - 1`` in place
    (pool aliased/donated, scratch-row routing — no pool-sized copy) and
    write the same tokens, optionally TX-encrypted, to the gather output
    while they are still in registers.

    ``n_buffers >= 2`` enables DMA pipelining: the stream (and RX
    keystream) operands move to off-chip ``ANY`` memory and each [1, S]
    row is staged into one of ``n_buffers`` VMEM slots by an async copy
    issued one row ahead of compute (double/quad buffering; depth chosen
    by :mod:`repro.kernels.dma_profile`). ``n_buffers == 0`` compiles the
    plain blocked layout.

    Caller invariant (``_recv_batch_fused`` streams and
    ``testing.fused_round_case`` hold it): ``S >= meta_max + pps * page``,
    so every payload page window lies inside the stream."""
    b, s = stream.shape[0], stream.shape[-1]      # [B, S] or [B, 1, S]
    p_ext, page = pool.shape[0], pool.shape[-1]
    pps = tables.shape[1]
    assert pps >= 1, "fused_round needs >= 1 table column (pad tables)"
    assert s >= meta_max + pps * page, (s, meta_max, pps, page)
    assert n_buffers == 0 or n_buffers >= 2, n_buffers
    _check_tiling(interpret, page=page, meta_max=meta_max)
    has_ks = keystream is not None
    has_txks = tx_keystream is not None
    has_policy = cond_off is not None
    has_live = live is not None
    has_meta_ks = meta_ks is not None
    if has_ks:
        assert _rows(keystream).shape == (b, 1, s), (keystream.shape, s)
    if has_txks:
        assert _rows(tx_keystream).shape == (b, 1, pps * page), \
            tx_keystream.shape
    r = k = 0
    if has_policy:
        r, k = cond_off.shape
        if has_meta_ks:
            assert meta_ks.shape == (b, meta_max), (meta_ks.shape, b, meta_max)
    else:
        assert not (has_live or has_meta_ks)
    scratch = p_ext - 1

    def _pool_index(b_, j, ml, tl, tbl):
        # invalid table entries (-1) and the metadata step are routed to the
        # scratch row so no real page is ever revisited by a non-owner step
        pid = tbl[b_ * pps + jnp.maximum(j - 1, 0)]
        return (jnp.where((j == 0) | (pid < 0), scratch, pid), 0, 0)

    if n_buffers:
        # stream rows live off-chip and are staged by the kernel's own DMAs
        stream_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    else:
        stream_spec = pl.BlockSpec((None, 1, s),
                                   lambda b_, j, ml, tl, tbl: (b_, 0, 0))
    row_spec = pl.BlockSpec((None, 1, meta_max),
                            lambda b_, j, ml, tl, tbl: (b_, 0, 0))
    gather_spec = pl.BlockSpec(
        (None, 1, page),
        lambda b_, j, ml, tl, tbl: (b_, 0, jnp.maximum(j - 1, 0)))
    page_spec = pl.BlockSpec((None, 1, page), _pool_index)
    in_specs = [stream_spec]
    operands = [_rows(stream)]
    if has_ks:
        in_specs.append(stream_spec)        # keystream rides the stream layout
        operands.append(_rows(keystream))
    if has_txks:
        in_specs.append(gather_spec)        # payload-relative TX keystream
        operands.append(_rows(tx_keystream))
    in_specs.append(page_spec)
    operands.append(_rows(pool))
    # pool operand index counts the 3 scalar-prefetch args
    pool_operand = 3 + len(operands) - 1
    if has_policy:
        table_spec = pl.BlockSpec((r, k), lambda b_, j, ml, tl, tbl: (0, 0))
        in_specs += [table_spec, table_spec, table_spec]
        operands += [cond_off, cond_lo, cond_hi]
        if has_meta_ks:
            in_specs.append(row_spec)
            operands.append(_rows(meta_ks))
        if has_live:
            assert live.shape == (r,), (live.shape, r)
            in_specs.append(
                pl.BlockSpec((r, 1), lambda b_, j, ml, tl, tbl: (0, 0)))
            operands.append(jnp.asarray(live, jnp.int32).reshape(r, 1))

    out_specs = [row_spec, page_spec, gather_spec]
    out_shape = [jax.ShapeDtypeStruct((b, 1, meta_max), stream.dtype),
                 jax.ShapeDtypeStruct((p_ext, 1, page), pool.dtype),
                 jax.ShapeDtypeStruct((b, 1, pps * page), stream.dtype)]
    if has_policy:
        out_specs.append(pl.BlockSpec(
            (None, 1, LANE), lambda b_, j, ml, tl, tbl: (b_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, 1, LANE), jnp.int32))
    scratch_shapes = []
    if n_buffers:
        scratch_shapes += [pltpu.VMEM((n_buffers, 1, s), stream.dtype),
                           pltpu.SemaphoreType.DMA((n_buffers,))]
        if has_ks:
            scratch_shapes += [pltpu.VMEM((n_buffers, 1, s), stream.dtype),
                               pltpu.SemaphoreType.DMA((n_buffers,))]

    res = pl.pallas_call(
        functools.partial(_fused_round_kernel, page=page, pps=pps,
                          meta_max=meta_max, b_rows=b, has_ks=has_ks,
                          has_txks=has_txks, has_policy=has_policy,
                          has_live=has_live, has_meta_ks=has_meta_ks,
                          n_buffers=n_buffers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, 1 + pps),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        input_output_aliases={pool_operand: 1},
        interpret=interpret,
    )(meta_len, total_len, tables.reshape(-1), *operands)
    verdict = res[3][:, 0, 0] if has_policy else None
    return (res[0].reshape(b, meta_max), res[1].reshape(pool.shape), verdict,
            res[2].reshape(b, pps * page))


_FUSED_STATICS = ("meta_max", "interpret", "n_buffers")

#: default fused-round entry — pool buffer NOT donated (parity checks)
fused_round = jax.jit(_fused_round_impl, static_argnames=_FUSED_STATICS)

#: donating fused-round entry — the pool argument (index 3) is donated so
#: the resident device pool is updated truly in place across one-kernel
#: rounds (see DevicePool.fused_round_device)
fused_round_donated = jax.jit(_fused_round_impl,
                              static_argnames=_FUSED_STATICS,
                              donate_argnums=(3,))
