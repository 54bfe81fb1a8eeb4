"""Serving engines — the paper's four stacks, as continuous-batching LLM
servers (see DESIGN.md §2 datapaths):

* ``LibraEngine``    — selective copy: paged anchored KV (donated, in-place),
                       parser policy splits header/payload, only token ids +
                       O(pages) int32 metadata cross the host boundary, VPI
                       handles support zero-copy forwarding/prefix sharing.
* ``StandardEngine`` — standard stack: contiguous KV re-materialised every
                       step (undonated buffer = the per-message full copy),
                       full logits shipped to the host *per connection*.
* ``CopierEngine``   — Copier [24]: identical data volume, but all per-
                       connection transfers batched into one fused copy per
                       step (the single async kernel copy).
* ``StaticEngine``   — F-Stack/DPDK analogue: fast fixed preallocated dense
                       buffers; a fixed memory budget caps concurrency, so
                       large payloads collapse attainable batch (the paper's
                       F-Stack large-payload inversion).

All engines expose the same submit()/run() interface and an EngineStats
block mirroring the paper's Figure 9 cost categories.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.core.anchor_pool import PoolExhausted
from repro.core.parser import TokenStreamParser
from repro.core.stack import LibraStack
from repro.models.attention import plan_decode_sharding
from repro.serving.kv_cache import PagedKVPool, SeqHandle


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # full token stream (header + payload)
    header_len: int
    max_new_tokens: int
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    output: List[int] = dataclasses.field(default_factory=list)
    handle: Optional[SeqHandle] = None
    slot: int = -1

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new_tokens


@dataclasses.dataclass
class EngineStats:
    # host<->device boundary traffic (the kernel/user boundary analogue)
    h2d_bytes: int = 0           # tokens + metadata uploaded
    d2h_bytes: int = 0           # tokens / logits downloaded
    d2h_calls: int = 0           # per-connection transfer count
    # device-side payload movement
    payload_copy_bytes: int = 0  # full-cache copies (Std/Copier copy tax)
    anchored_bytes: int = 0      # payload written once into the pool
    zero_copy_bytes: int = 0     # ownership transfers (VPI forwarding)
    steps: int = 0
    prefills: int = 0
    completed: int = 0
    alloc_events: int = 0


def _pad_to(x: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(x) >= n:
        return x[:n]
    return np.concatenate([x, np.full(n - len(x), fill, x.dtype)])


class _EngineBase:
    name = "base"

    def __init__(self, model, params, *, max_batch: int = 8,
                 max_len: int = 512, parser: Optional[TokenStreamParser] = None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.parser = parser or TokenStreamParser(header_len=8)
        self.stats = EngineStats()
        self.waiting: List[Request] = []
        self.active: List[Request] = []
        self.completed: List[Request] = []
        self._rid = 0
        self.mesh = jax.make_mesh((1, 1), ("data", "model"),
                                  axis_types=(AxisType.Auto,) * 2)

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> Request:
        self._rid += 1
        r = Request(self._rid, np.asarray(prompt, np.int32),
                    self.parser.parse(prompt).meta_len, max_new_tokens,
                    submitted_at=time.perf_counter())
        self.waiting.append(r)
        return r

    def run(self, max_steps: int = 10 ** 6) -> List[Request]:
        steps = 0
        while (self.waiting or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.completed

    # latency metrics -------------------------------------------------------
    def p99_latency(self) -> float:
        lats = sorted((r.done_at - r.submitted_at) for r in self.completed
                      if r.done_at)
        if not lats:
            return 0.0
        return lats[min(len(lats) - 1, int(0.99 * len(lats)))]

    def throughput_tokens(self) -> int:
        return sum(len(r.output) for r in self.completed)


# ---------------------------------------------------------------------------
# Libra engine
# ---------------------------------------------------------------------------

class LibraEngine(_EngineBase):
    name = "libra"

    def __init__(self, model, params, *, max_batch: int = 8, max_len: int = 512,
                 page_size: int = 16, parser=None, pool_pages: int = 0,
                 stack: Optional[LibraStack] = None,
                 kv_pool: Optional[PagedKVPool] = None):
        super().__init__(model, params, max_batch=max_batch, max_len=max_len,
                         parser=parser)
        self.page_size = page_size
        b_axis, combine = plan_decode_sharding(max_batch, self.mesh)
        self.b_axis, self.combine = b_axis, combine
        # one LibraStack per engine "kernel": it owns the page allocator, the
        # VPI registry, the tick clock, and the copy counters. A shared
        # ``stack`` pools that host state across engines; zero-copy
        # CROSS-ENGINE handoff additionally needs the device KV itself
        # shared — pass the first engine's ``kv_pool`` to the second
        # (handles forwarded into an engine with its own pool would index a
        # different, zero-filled device array).
        if stack is None:
            pages = pool_pages or (max_batch * (max_len // page_size + 2) + 4)
            stack = LibraStack(n_shards=1, pages_per_shard=pages,
                               page_size=page_size)
        elif pool_pages:
            raise ValueError("pool_pages conflicts with an external stack: "
                             "the stack's allocator defines the geometry")
        assert stack.alloc.page_size == page_size, \
            (stack.alloc.page_size, page_size)
        self.stack = stack
        if kv_pool is not None:
            assert kv_pool.alloc is stack.alloc, \
                "a shared kv_pool must be backed by the shared stack's allocator"
            self.pool = kv_pool
        else:
            self.pool = PagedKVPool(model, page_size=page_size,
                                    alloc=stack.alloc, registry=stack.registry)
        self.pps = max_len // page_size + 2
        # parking page for inactive slots (keeps decode NaN-free)
        self._parking = self.pool.alloc.alloc_page(0, 0)
        if self.cfg.family == "hybrid":
            d_inner = self.cfg.ssm_expand * self.cfg.d_model
            self.ssm_state = {
                "ssm": jnp.zeros((self.cfg.num_layers, max_batch, d_inner,
                                  self.cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((self.cfg.num_layers, max_batch,
                                   self.cfg.ssm_conv - 1, d_inner), jnp.float32),
            }
        else:
            self.ssm_state = None
        self._jit_decode = jax.jit(
            partial(self.model.decode_step, mesh=self.mesh, batch_axis=b_axis,
                    combine_axes=combine, compute_dtype=jnp.float32),
            donate_argnums=(3,))
        self._jit_prefill_cache: Dict[Tuple[int, int], object] = {}

    # -- ingress (prefill anchors the payload) -------------------------------
    def _prefill_group(self, group: List[Request]) -> None:
        pad_b = len(group)
        s = max(len(r.prompt) for r in group)
        s = max(self.page_size, -(-s // self.page_size) * self.page_size)
        handles = [r.handle for r in group]  # allocated at admission
        tokens = np.stack([_pad_to(r.prompt, s) for r in group])
        seq_lens = np.array([len(r.prompt) for r in group], np.int32)
        tables, _ = self.pool.batch_tables(handles, self.pps)
        tsh, tsl, toff, tval = self.pool.token_coords(handles, s)

        key = (pad_b, s)
        if key not in self._jit_prefill_cache:
            self._jit_prefill_cache[key] = jax.jit(
                partial(self.model.prefill, mesh=self.mesh,
                        batch_axis=self.b_axis, combine_axes=self.combine,
                        compute_dtype=jnp.float32),
                donate_argnums=(3,))
        first, new_pool = self._jit_prefill_cache[key](
            self.params, jnp.array(tokens), jnp.array(seq_lens),
            self.pool.pool, jnp.array(tables), jnp.array(tsh),
            jnp.array(tsl), jnp.array(toff), jnp.array(tval))
        self.pool.pool = new_pool
        first = np.asarray(first)
        now = time.perf_counter()
        for i, r in enumerate(group):
            r.output.append(int(first[i]))
            r.first_token_at = now
        # stats: selective copy — tokens up, ONLY sampled ids down
        self.stats.h2d_bytes += tokens.nbytes + tables.nbytes + tsh.nbytes * 3
        self.stats.d2h_bytes += first.nbytes
        self.stats.d2h_calls += 1
        self.stats.anchored_bytes += int(
            sum(seq_lens) * self._kv_bytes_per_token())
        self.stats.prefills += 1
        self.stats.alloc_events += len(group)

    def _kv_bytes_per_token(self) -> int:
        c = self.cfg
        return c.num_layers * 2 * c.num_kv_heads * c.head_dim * 4

    def step(self) -> None:
        # each engine step advances the stack clock: deferred teardowns from
        # closed connections expire on the engine's cadence (§A.4)
        self.stack.tick()
        # admit
        free = self.max_batch - len(self.active)
        group = []
        while self.waiting and free > 0:
            r = self.waiting[0]
            try:
                # reserve prompt + decode room at admission so an admitted
                # request can always finish (vLLM-style admission soundness);
                # allocation here keeps multi-request waves accounted
                r.handle = self.pool.anchor_sequence(
                    len(r.prompt), r.header_len, reserve=r.max_new_tokens)
            except PoolExhausted:
                break
            c = self.stack.counters
            c.anchored += len(r.prompt) - r.header_len
            c.meta_copied += r.header_len
            c.vpi_injected += 1
            c.allocs += 1
            self.waiting.pop(0)
            group.append(r)
            free -= 1
        if group:
            self._prefill_group(group)
            now = time.perf_counter()
            for r in group:  # gen=1 requests complete at prefill
                if r.done:
                    r.done_at = now
                    self.pool.release(r.handle)
                    self.completed.append(r)
                    self.stats.completed += 1
                else:
                    self.active.append(r)
        if not self.active:
            return

        # decode one token for every active request
        b = self.max_batch
        handles = []
        seq_lens = np.zeros(b, np.int32)
        tokens = np.zeros(b, np.int32)
        slot_req: List[Optional[Request]] = [None] * b
        for i, r in enumerate(self.active):
            r.slot = i
            slot_req[i] = r
            pos = len(r.prompt) + len(r.output) - 1
            self.pool.extend(r.handle, pos + 1)
            handles.append(r.handle)
            seq_lens[i] = pos
            tokens[i] = r.output[-1]
        # inactive slots park on a scratch page
        parking = SeqHandle(0, [self._parking], 0, 0)
        while len(handles) < b:
            handles.append(parking)
        tables, page_pos = self.pool.batch_tables(handles, self.pps)
        wsh, wsl = self.pool.write_coords(handles, seq_lens.tolist())

        out = self._jit_decode(self.params, jnp.array(tokens),
                               jnp.array(seq_lens), self.pool.pool,
                               jnp.array(tables), jnp.array(page_pos),
                               jnp.array(wsh), jnp.array(wsl),
                               ssm_state=self.ssm_state)
        next_tokens, self.pool.pool, new_ssm = out
        if new_ssm is not None:
            self.ssm_state = new_ssm
        next_tokens = np.asarray(next_tokens)

        self.stats.h2d_bytes += (tokens.nbytes + seq_lens.nbytes + tables.nbytes
                                 + page_pos.nbytes + wsh.nbytes + wsl.nbytes)
        self.stats.d2h_bytes += next_tokens.nbytes
        self.stats.d2h_calls += 1
        self.stats.anchored_bytes += len(self.active) * self._kv_bytes_per_token()
        self.stats.steps += 1

        now = time.perf_counter()
        still = []
        for r in self.active:
            r.output.append(int(next_tokens[r.slot]))
            if r.done:
                r.done_at = now
                self.pool.release(r.handle)
                self.completed.append(r)
                self.stats.completed += 1
            else:
                still.append(r)
        self.active = still

    # -- egress: zero-copy forwarding (VPI handoff) ---------------------------
    def forward_handle(self, r: Request) -> SeqHandle:
        """Proxy forwarding: hand the anchored context to another consumer
        without moving payload bytes (refcounted ownership share)."""
        h = self.pool.share(r.handle)
        self.stats.zero_copy_bytes += h.seq_len * self._kv_bytes_per_token()
        self.stack.counters.zero_copied += h.seq_len
        return h

    def release_handle(self, h: SeqHandle) -> None:
        """Drop a forwarded handle (the backend finished with the shared
        context). Facade call so call-sites never touch the pool."""
        self.pool.release(h)


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------

class StandardEngine(_EngineBase):
    """Contiguous KV re-copied per step + per-connection logits transfers."""
    name = "standard"
    donate_cache = False
    fused_d2h = False

    def __init__(self, model, params, *, max_batch: int = 8, max_len: int = 512,
                 parser=None):
        super().__init__(model, params, max_batch=max_batch, max_len=max_len,
                         parser=parser)
        c = self.cfg
        self.cache = jnp.zeros((c.num_layers, max_batch, max_len, 2,
                                c.num_kv_heads, c.head_dim), jnp.float32)
        self.slot_free = list(range(max_batch))
        donate = (3,) if self.donate_cache else ()
        self._jit_decode = jax.jit(
            lambda p, t, s, cache: model.decode_step_dense(
                p, t, s, cache, compute_dtype=jnp.float32),
            donate_argnums=donate)
        self._jit_prefill_cache: Dict[Tuple[int, int], object] = {}

    def _cache_bytes(self) -> int:
        return int(np.prod(self.cache.shape)) * 4

    def step(self) -> None:
        group = []
        while self.waiting and self.slot_free:
            r = self.waiting.pop(0)
            r.slot = self.slot_free.pop(0)
            group.append(r)
        if group:
            s = max(self.cfg.head_dim // self.cfg.head_dim * 8,
                    max(len(r.prompt) for r in group))
            key = (len(group), s)
            if key not in self._jit_prefill_cache:
                self._jit_prefill_cache[key] = jax.jit(partial(
                    self.model.prefill_dense, max_len=self.max_len,
                    compute_dtype=jnp.float32))
            tokens = np.stack([_pad_to(r.prompt, s) for r in group])
            seq_lens = np.array([len(r.prompt) for r in group], np.int32)
            first, kv = self._jit_prefill_cache[key](
                self.params, jnp.array(tokens), jnp.array(seq_lens))
            first = np.asarray(first)
            now = time.perf_counter()
            for i, r in enumerate(group):
                self.cache = self.cache.at[:, r.slot].set(kv[:, i])
                r.output.append(int(first[i]))
                r.first_token_at = now
                if r.done:  # gen=1 completes at prefill
                    r.done_at = now
                    self.slot_free.append(r.slot)
                    self.completed.append(r)
                    self.stats.completed += 1
                else:
                    self.active.append(r)
            self.stats.h2d_bytes += tokens.nbytes
            self.stats.d2h_bytes += first.nbytes
            self.stats.prefills += 1
            # the prefill KV lands in a fresh contiguous buffer: full copy
            self.stats.payload_copy_bytes += int(np.prod(np.shape(kv))) * 4
            self.stats.alloc_events += len(group)
        if not self.active:
            return

        b = self.max_batch
        tokens = np.zeros(b, np.int32)
        seq_lens = np.zeros(b, np.int32)
        for r in self.active:
            tokens[r.slot] = r.output[-1]
            seq_lens[r.slot] = len(r.prompt) + len(r.output) - 1
        logits, new_cache = self._jit_decode(self.params, jnp.array(tokens),
                                             jnp.array(seq_lens), self.cache)
        self.cache = new_cache
        if not self.donate_cache:
            # undonated contiguous cache: XLA materialises a fresh copy —
            # the standard stack's per-message payload copy
            self.stats.payload_copy_bytes += self._cache_bytes()
        # recv path: logits cross to the host
        if self.fused_d2h:
            host_logits = np.asarray(logits)
            self.stats.d2h_bytes += host_logits.nbytes
            self.stats.d2h_calls += 1
        else:
            host_logits = np.zeros((b, logits.shape[-1]), np.float32)
            for r in self.active:  # per-connection recv copies
                host_logits[r.slot] = np.asarray(logits[r.slot])
                self.stats.d2h_bytes += host_logits[r.slot].nbytes
                self.stats.d2h_calls += 1
        self.stats.h2d_bytes += tokens.nbytes + seq_lens.nbytes
        self.stats.steps += 1

        now = time.perf_counter()
        still = []
        for r in self.active:
            r.output.append(int(np.argmax(host_logits[r.slot])))
            if r.done:
                r.done_at = now
                self.slot_free.append(r.slot)
                self.completed.append(r)
                self.stats.completed += 1
            else:
                still.append(r)
        self.active = still


class CopierEngine(StandardEngine):
    """Copier [24]: same volume, fused into one async copy per step."""
    name = "copier"
    donate_cache = False
    fused_d2h = True


class StaticEngine(StandardEngine):
    """F-Stack analogue: preallocated fixed-budget buffers (fast per step,
    concurrency collapses with payload size)."""
    name = "static"
    donate_cache = True
    fused_d2h = True

    def __init__(self, model, params, *, memory_budget: int, max_len: int = 512,
                 parser=None):
        c = model.cfg
        per_slot = c.num_layers * max_len * 2 * c.num_kv_heads * c.head_dim * 4
        max_batch = max(1, memory_budget // per_slot)
        super().__init__(model, params, max_batch=max_batch, max_len=max_len,
                         parser=parser)
