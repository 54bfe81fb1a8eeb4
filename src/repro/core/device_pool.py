"""Device-resident payload pool — the kernel-retained skb pages, kept on
the fast side of the boundary.

Libra's premise is that payloads are written once into the kernel-retained
pool and never touched again. The legacy :class:`~repro.core.stream.
TokenPool` honours that on the host but betrays it at the device boundary:
every batched device round re-uploads the whole pool (``astype(int32)``)
and syncs the touched rows back — two O(pool) crossings per scheduling
round, exactly the "bulk data crosses the boundary" failure mode the paper
eliminates (and the regime kernel-resident L7 datapaths like XLB win in).

:class:`DevicePool` keeps the ``[P+1, page]`` pool **resident as a jax
array across rounds**: the fused ingress kernel's donation updates it in
place, the fused egress gather reads it in place, and only O(batch) data
(the round's stream/tables/keystreams up, the gathered payloads down) ever
crosses the boundary. The host ``int64`` mirror inherited from
``TokenPool`` stays available for the scalar datapaths and the tests via
**dirty-row tracking**:

* ``host-dirty`` rows — host truth, device copy stale/unfaithful. Set by
  scalar-path writes (``write_payload``/``write_payload_batch``) and for
  rows whose int64 content does not survive the int32 device dtype.
  Uploaded lazily (O(rows)) when a device round touches them; a round that
  would need an out-of-range row raises :class:`DeviceRangeError` so the
  caller can bounce that round to the int64-exact host path.
* ``device-dirty`` rows — device truth, host mirror stale. Set by device
  anchoring rounds. Materialized lazily (O(rows)) when a host read/write
  or a whole-pool view (``data``/``flat_with_scratch``) needs them.

Every boundary crossing is counted in :attr:`TokenPool.xfer`
(``h2d_tokens``/``d2h_tokens``); ``pool_syncs`` — the O(pool) crossing
counter — stays at zero for this class by construction, and the batched-
datapath tests assert it.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core import trace
from repro.core.anchor_pool import AnchorPool, PageRef
from repro.core.stream import TokenPool

I32_MIN, I32_MAX = -(1 << 31), (1 << 31) - 1


class DeviceRangeError(Exception):
    """A round needs pool rows / operands whose int64 values do not survive
    the int32 device dtype — serve it from the int64-exact host path."""


class DevicePool(TokenPool):
    """A :class:`TokenPool` whose batched device rounds run against one
    resident jax array instead of per-round whole-pool bounces."""

    def __init__(self, alloc: AnchorPool):
        super().__init__(alloc)
        # jax.Array [P+1, 1, page] int32: the kernels' row view of the
        # [P+1, page] pool (one page per tiled row), so device rounds move
        # whole pages with no relayout of the pool
        self._dev = None
        rows = self._flat.shape[0]
        self._host_dirty = np.zeros((rows,), bool)
        self._dev_dirty = np.zeros((rows,), bool)
        # DMA staging ring depth for the fused one-kernel round (0 =
        # blocked layout); set from kernels.dma_profile.auto_buffer_depth
        # or the LIBRA_FUSED_BUFFERS env var by the deployment
        self.fused_buffers = 0

    # -- residency -----------------------------------------------------------
    @property
    def resident(self) -> bool:
        """True once the device copy exists (first device round)."""
        return self._dev is not None

    def block_until_ready(self) -> None:
        """Wait until every dispatched device round has finished writing
        the resident pool (timing hook; no-op before residency)."""
        if self._dev is not None:
            self._dev.block_until_ready()

    def dirty_rows(self) -> np.ndarray:
        """Rows whose truth currently lives on the device (host mirror
        stale) — telemetry/testing hook."""
        return np.flatnonzero(self._dev_dirty)

    def _ensure_device(self) -> None:
        """Create the resident device pool from the host mirror — ONE
        O(pool) upload for the lifetime of the pool, not one per round.
        Rows whose int64 content does not fit int32 stay host-truth."""
        if self._dev is not None:
            return
        import jax.numpy as jnp

        flat = self._flat
        oob = ((flat < I32_MIN) | (flat > I32_MAX)).any(axis=1)
        self._host_dirty |= oob
        self._dev = jnp.asarray(flat.astype(np.int32)[:, None, :])
        self.xfer["resident_init_tokens"] += flat.size

    def _upload_rows(self, rows: np.ndarray) -> None:
        """Make ``rows`` faithful on the device (host-dirty rows go up,
        O(rows) not O(pool)). Raises :class:`DeviceRangeError` — before
        touching anything — when a row's content cannot survive int32."""
        sel = rows[self._host_dirty[rows]]
        if len(sel) == 0:
            return
        vals = self._flat[sel]
        if vals.size and (vals.min() < I32_MIN or vals.max() > I32_MAX):
            raise DeviceRangeError("host-truth rows exceed int32")
        self._dev = self._dev.at[sel].set(vals.astype(np.int32)[:, None, :])
        self._host_dirty[sel] = False
        self.xfer["h2d_tokens"] += vals.size

    def _materialize_rows(self, rows: np.ndarray) -> None:
        """Pull device-truth ``rows`` back into the host mirror (lazy,
        O(rows)): int32 device values are exact in the int64 mirror."""
        sel = rows[self._dev_dirty[rows]]
        if len(sel) == 0:
            return
        host = np.asarray(self._dev[sel])[:, 0, :].astype(np.int64)
        self._flat[sel] = host
        self._dev_dirty[sel] = False
        self.xfer["d2h_tokens"] += host.size

    def materialize(self) -> None:
        """Sync every device-truth row into the host mirror (tests and
        whole-pool consumers; scalar datapaths use the per-row lazy path)."""
        self._materialize_rows(np.arange(self._flat.shape[0]))

    def _rows_of(self, pages: Sequence[PageRef]) -> np.ndarray:
        return np.unique(np.fromiter(
            (self.alloc.flat_pid(pg) for pg in pages), np.int64,
            count=len(pages)))

    # -- host views materialize lazily ----------------------------------------
    # Both whole-pool views keep TokenPool's write-through contract: the
    # caller may mutate what they return. A write through the view cannot
    # be observed, so once resident the ENTIRE pool must be treated as
    # host-truth after handing one out — later device rounds lazily re-
    # upload whichever of those rows they actually touch (still O(rows)).
    @property
    def data(self) -> np.ndarray:
        self.materialize()
        if self._dev is not None:
            self._host_dirty[:] = True
        return self._data_view

    @property
    def flat_with_scratch(self) -> np.ndarray:
        self.materialize()
        if self._dev is not None:
            self._host_dirty[:] = True
        return self._flat

    # -- host (scalar-path) writes/reads keep the mirror authoritative --------
    def write_payload(self, pages: List[PageRef], payload: np.ndarray,
                      keystream: Optional[np.ndarray] = None) -> None:
        if self._dev is not None and pages and len(payload):
            rows = self._rows_of(pages)
            # a partial-page host write must land on the row's true content
            self._materialize_rows(rows)
            self._host_dirty[rows] = True
        super().write_payload(pages, payload, keystream=keystream)

    def write_payload_batch(self, seqs, keystreams=None) -> None:
        if self._dev is not None:
            all_pages = [pg for pages, p in seqs if len(p) and pages
                         for pg in pages]
            if all_pages:
                rows = self._rows_of(all_pages)
                self._materialize_rows(rows)
                self._host_dirty[rows] = True
        super().write_payload_batch(seqs, keystreams=keystreams)

    def read_payload(self, pages: List[PageRef], length: int,
                     keystream: Optional[np.ndarray] = None) -> np.ndarray:
        if self._dev is not None and pages and length:
            self._materialize_rows(self._rows_of(pages))
        return super().read_payload(pages, length, keystream=keystream)

    def read_payload_batch(self, seqs, keystreams=None):
        if self._dev is not None:
            all_pages = [pg for pages, ln in seqs if ln and pages
                         for pg in pages]
            if all_pages:
                self._materialize_rows(self._rows_of(all_pages))
        return super().read_payload_batch(seqs, keystreams=keystreams)

    # -- device data plane: resident, zero O(pool) crossings -------------------
    def anchor_batch_device(self, stream: np.ndarray, meta_len: np.ndarray,
                            total_len: np.ndarray, tables: np.ndarray, *,
                            meta_max: int, impl: str,
                            keystream: Optional[np.ndarray] = None) -> None:
        """One batched ingress round, entirely on-device: upload O(batch)
        operands (plus any host-dirty rows the round overwrites), run the
        fused kernel against the resident pool, and keep the donated result
        resident — **nothing O(pool) crosses the boundary, nothing syncs
        back**. Touched rows become device-truth (lazy host views).

        The resident pool is **donated through the outer jit**
        (``donate_pool=True``): the round updates the one live pool buffer
        in place instead of allocating an output copy next to the input —
        verified per round by comparing buffer pointers
        (``xfer['donated_rounds']``)."""
        from repro.kernels import ops

        self._ensure_device()
        rows = np.unique(tables[tables >= 0]).astype(np.int64)
        self._upload_rows(rows)               # may raise DeviceRangeError
        self.xfer["h2d_tokens"] += stream.size + tables.size \
            + meta_len.size + total_len.size \
            + (keystream.size if keystream is not None else 0)
        donated_in = self._dev
        new_meta, new_pool = ops.selective_copy(
            stream, meta_len, total_len, self._dev, tables,
            meta_max=meta_max, impl=impl, reserved_scratch=True,
            keystream=keystream, donate_pool=True)
        del new_meta  # host buffers keep the int64-exact metadata
        self._dev = new_pool
        # the donation's guarantee: XLA consumed (deleted) the input pool
        # buffer, so exactly ONE pool allocation stays live across the
        # round — not an input + an output copy
        try:
            if donated_in is not new_pool and donated_in.is_deleted():
                self.xfer["donated_rounds"] += 1
        except Exception:  # pragma: no cover - backend without the API
            pass
        self._dev_dirty[rows] = True
        self.xfer["device_rounds"] += 1
        self.xfer["anchor_rounds"] += 1

    def fused_round_device(
        self, stream: np.ndarray, meta_len: np.ndarray,
        total_len: np.ndarray, tables: np.ndarray, *, meta_max: int,
        impl: str, keystream: Optional[np.ndarray] = None,
        tx_keystream: Optional[np.ndarray] = None,
        cond_off: Optional[np.ndarray] = None,
        cond_lo: Optional[np.ndarray] = None,
        cond_hi: Optional[np.ndarray] = None,
        live: Optional[np.ndarray] = None,
        meta_ks: Optional[np.ndarray] = None,
        n_buffers: int = 0,
    ) -> Tuple[Optional[np.ndarray], np.ndarray]:
        """The **one-kernel scheduling round**: anchor + hw-kTLS keystream
        XOR + policy first-match + egress gather in a SINGLE launch against
        the resident pool — ``xfer['fused_rounds']`` counts exactly one
        ``device_rounds`` bump where the multi-pass path pays three
        (anchor + policy match + gather). Upload is O(batch) operands plus
        any host-dirty rows the round overwrites; only the verdict column
        and the gathered payload block come down. Touched rows become
        device-truth, and the resident pool is donated through the outer
        jit exactly like :meth:`anchor_batch_device`.

        Returns ``(verdict [B] | None, gathered [B, pps*page] int64)`` —
        the int64-exact metadata stays host-side (the caller already holds
        it), and ``gathered`` is the round's speculative egress block
        (TX-encrypted when ``tx_keystream`` is supplied)."""
        from repro.kernels import ops

        with trace.span("pool.call"):
            self._ensure_device()
            rows = np.unique(tables[tables >= 0]).astype(np.int64)
            self._upload_rows(rows)           # may raise DeviceRangeError
            self.xfer["h2d_tokens"] += stream.size + tables.size \
                + meta_len.size + total_len.size \
                + sum(op.size for op in (keystream, tx_keystream, cond_off,
                                         cond_lo, cond_hi, live, meta_ks)
                      if op is not None)
            donated_in = self._dev
            new_meta, new_pool, verdict, gathered = ops.fused_round(
                stream, meta_len, total_len, self._dev, tables,
                meta_max=meta_max, impl=impl, keystream=keystream,
                tx_keystream=tx_keystream, cond_off=cond_off,
                cond_lo=cond_lo, cond_hi=cond_hi, live=live,
                meta_ks=meta_ks, n_buffers=n_buffers, donate_pool=True)
        del new_meta  # host buffers keep the int64-exact metadata
        self._dev = new_pool
        try:
            if donated_in is not new_pool and donated_in.is_deleted():
                self.xfer["donated_rounds"] += 1
        except Exception:  # pragma: no cover - backend without the API
            pass
        self._dev_dirty[rows] = True
        self.xfer["device_rounds"] += 1
        self.xfer["anchor_rounds"] += 1
        self.xfer["fused_rounds"] += 1
        # the gathered block's transfer waits for the kernel to finish
        with trace.span("pool.wait"):
            host_out = np.asarray(gathered)
            host_verdict = None
            if verdict is not None:
                host_verdict = np.asarray(verdict)
        self.xfer["d2h_tokens"] += host_out.size
        if host_verdict is not None:
            self.xfer["d2h_tokens"] += host_verdict.size
        with trace.span("pool.widen"):
            return host_verdict, host_out.astype(np.int64)

    def gather_batch_device(self, tables: np.ndarray, lengths: np.ndarray, *,
                            impl: str,
                            keystream: Optional[np.ndarray] = None,
                            ) -> np.ndarray:
        """One batched egress round: fused gather straight off the resident
        pool. Only the gathered payload block (O(batch)) comes down — the
        bytes that are leaving on the wire anyway."""
        from repro.kernels import ops

        with trace.span("pool.call"):
            self._ensure_device()
            rows = np.unique(tables[tables >= 0]).astype(np.int64)
            self._upload_rows(rows)           # may raise DeviceRangeError
            self.xfer["h2d_tokens"] += tables.size + lengths.size \
                + (keystream.size if keystream is not None else 0)
            out = ops.selective_gather(self._dev, tables, lengths,
                                       impl=impl, keystream=keystream)
        with trace.span("pool.wait"):
            host = np.asarray(out)
        self.xfer["d2h_tokens"] += host.size
        self.xfer["device_rounds"] += 1
        with trace.span("pool.widen"):
            return host.astype(np.int64)
