"""Continuous-batching scheduler: request queue → slot map → ONE jitted
decode step over a fixed ``[max_batch]`` slot array.

The same pad-to-max + traced-validity-mask idiom the elastic trainer
uses for variable worker counts (DESIGN.md §Elastic) applied to serving:
the decode step is compiled ONCE for ``[max_batch]`` slots; per-slot
positions (``decode_step``'s ``[B]`` pos vector) and a traced live mask
let requests join and finish at any step with ZERO recompiles.  Dead
slots keep computing (they re-write their own last cache entry — a
no-op) and their outputs are masked off on the host; admission scatters
a freshly-prefilled batch=1 cache slice into a free slot with a traced
slot index (serving/cache.py).

Prefill policy: attention-only, non-windowed configs pad prompts to
power-of-two buckets (one compile per bucket; right-pad garbage is
overwritten-before-read under the ``idx <= pos`` validity mask).
Recurrent (rwkv/mamba) or windowed configs prefill at EXACT length —
padding would corrupt the carried O(1) state / ring buffer — costing one
compile per distinct prompt length (DESIGN.md §Serve).

MoE: dispatch drops no tokens, so dead slots in batched decode add
routed pairs but change no live slot's output.

Stalls + timeouts (DESIGN.md §Faults): a slot can stop making progress
(wedged device — injected by the ``slot_stall`` fault via
``inject_stall``).  Stalled slots are masked out of the live decode set
(the same traced mask, so no recompile); a ``request_timeout`` > 0 arms
the watchdog: a slot that makes no progress for that many scheduler
ticks is torn down and its request REQUEUED from scratch at the front
of the queue (generated tokens discarded — the cache slot may be the
wedged resource), counted in ``metrics.requeues``.  Every request
therefore eventually completes or requeues-then-completes; nothing is
silently dropped.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import transformer as TF
from .cache import BlockTable, SlotCache
from .swap import HotSwapper
from .telemetry import ServeMetrics


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new: int
    tokens: list = dataclasses.field(default_factory=list)


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class ServeLoop:
    def __init__(self, cfg: ModelConfig, max_batch: int, max_len: int,
                 params=None, swapper: Optional[HotSwapper] = None,
                 dtype=None, metrics: Optional[ServeMetrics] = None,
                 mesh=None, cache_shardings=None,
                 request_timeout: int = 0):
        if (params is None) == (swapper is None):
            raise ValueError("pass exactly one of params / swapper")
        self.cfg, self.max_batch, self.max_len = cfg, max_batch, max_len
        self.swapper = swapper
        self._params = params
        self.metrics = metrics or ServeMetrics()
        # per-request watchdog: 0 = off; N = requeue a slot's request
        # after N scheduler ticks without decode progress
        self.request_timeout = request_timeout
        self.ticks = 0
        self._last_progress = np.zeros((max_batch,), np.int64)
        self._stalled_until = np.zeros((max_batch,), np.int64)
        dtype = dtype or (jnp.float32 if cfg.dtype == "float32"
                          else jnp.bfloat16)
        self.cache = SlotCache(cfg, max_batch, max_len, dtype, mesh,
                               cache_shardings)
        self.table = BlockTable(max_batch)
        self.queue: deque = deque()
        self.done: dict = {}
        self.steps = 0
        self._next_rid = 0
        # host-side slot state (tiny [B] vectors, shipped every step)
        self._tok = np.zeros((max_batch, 1), np.int32)
        self._pos = np.zeros((max_batch,), np.int32)
        self._remaining = np.zeros((max_batch,), np.int32)
        self._req_of_slot: list = [None] * max_batch
        seg_kinds = {s.kind for s in TF.segments(cfg)}
        self._bucket_ok = (not cfg.attention.window
                           and not (seg_kinds & {"rwkv", "hybrid"}))

        def prefill(params, tokens, last):
            small = TF.init_cache(cfg, 1, max_len, dtype)
            logits, small = TF.prefill_cache(cfg, params, tokens, small)
            first = jnp.argmax(logits[0, last], -1).astype(jnp.int32)
            return small, first

        def step(params, cache, tok, pos, live):
            logits, cache = TF.decode_step(cfg, params, cache, tok, pos)
            nxt = jnp.argmax(logits.reshape(max_batch, -1),
                             axis=-1).astype(jnp.int32)
            tok2 = jnp.where(live[:, None], nxt[:, None], tok)
            pos2 = jnp.where(live, jnp.minimum(pos + 1, max_len - 1), pos)
            return cache, tok2, pos2, nxt

        self._prefill = jax.jit(prefill)
        self._step = jax.jit(step, donate_argnums=(1,))

    # -- compile counters (zero-recompile assertions ride on these) ----
    def decode_compiles(self) -> int:
        return self._step._cache_size()

    def prefill_compiles(self) -> int:
        return self._prefill._cache_size()

    def params(self):
        return self.swapper.params() if self.swapper else self._params

    # -- request lifecycle ---------------------------------------------
    def submit(self, prompt, max_new: int, rid=None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        S = prompt.shape[0]
        if S >= self.max_len:
            raise ValueError(f"prompt length {S} >= max_len {self.max_len}")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        self.queue.append(Request(rid, prompt,
                                  min(max_new, self.max_len - S)))
        return rid

    def _admit(self):
        params = self.params()
        while self.queue and self.table.free_slots:
            req = self.queue.popleft()
            slot = self.table.alloc(req.rid)
            S = req.prompt.shape[0]
            Sb = min(_next_pow2(S), self.max_len - 1) if self._bucket_ok else S
            toks = np.zeros((1, Sb), np.int32)
            toks[0, :S] = req.prompt
            small, first = self._prefill(params, jnp.asarray(toks),
                                         jnp.int32(S - 1))
            self.cache.insert(small, slot)
            self.metrics.prefills += 1
            first = int(first)
            req.tokens.append(first)
            self._req_of_slot[slot] = req
            self._tok[slot, 0] = first
            self._pos[slot] = S
            self._remaining[slot] = req.max_new - 1
            self._last_progress[slot] = self.ticks
            if req.max_new <= 1:
                self._finish(slot)

    def _finish(self, slot: int):
        req = self._req_of_slot[slot]
        self._req_of_slot[slot] = None
        self._remaining[slot] = 0
        self.table.free(req.rid)
        self.done[req.rid] = np.asarray(req.tokens, np.int32)
        self.metrics.completed += 1

    # -- fault surface + watchdog --------------------------------------
    def inject_stall(self, slot: int, ticks: int) -> None:
        """Fault-injection hook (faults ``slot_stall``, benchmarks/
        chaos.py): mask ``slot`` out of the live decode set for the
        next ``ticks`` scheduler ticks — the slot stops making
        progress, as a wedged device would."""
        self._stalled_until[slot] = self.ticks + ticks

    def _requeue(self, slot: int) -> None:
        """Tear down a timed-out slot and restart its request from
        scratch at the queue front (tokens discarded — the slot, and
        anything cached in it, may be the wedged resource)."""
        req = self._req_of_slot[slot]
        self._req_of_slot[slot] = None
        self._remaining[slot] = 0
        self.table.free(req.rid)
        req.tokens = []
        self.queue.appendleft(req)
        self.metrics.requeues += 1

    def _check_timeouts(self) -> None:
        if not self.request_timeout:
            return
        for slot in range(self.max_batch):
            if (self._req_of_slot[slot] is not None
                    and self.ticks - self._last_progress[slot]
                    > self.request_timeout):
                self._requeue(slot)

    # -- main loop ------------------------------------------------------
    def run(self, on_step: Optional[Callable] = None) -> dict:
        """Drain the queue; returns {rid: generated tokens [max_new]}.

        ``on_step(loop, step_idx)`` fires after every decode step —
        hooks for tests/demos (e.g. publish a checkpoint mid-stream to
        force a hot swap under live decode).
        """
        idle = 0
        while self.queue or len(self.table):
            self.ticks += 1
            self._admit()
            if self.swapper is not None:
                if self.swapper.poll():
                    self.metrics.observe_swap(self.swapper.last_stall_s)
                self.metrics.gauge("ckpt_staleness_s",
                                   self.swapper.staleness_s())
                self.metrics.gauge("quarantined_ckpts",
                                   len(self.swapper.quarantined))
            self.metrics.queue_depth = len(self.queue)
            self.metrics.active_slots = len(self.table)
            self._check_timeouts()
            live_np = ((self._remaining > 0)
                       & (self._stalled_until <= self.ticks))
            if not live_np.any():
                # nothing can decode: stalled slots (or everything
                # finished at admit).  Ticks keep advancing so stalls
                # expire and the watchdog still fires; the idle cap
                # turns a stall with no timeout into a loud error
                # instead of a silent spin.
                idle += 1
                if idle > 100_000:
                    raise RuntimeError(
                        "serve loop wedged: no decode progress for "
                        "100000 ticks (stalled slots and no "
                        "request_timeout?)")
                continue
            idle = 0
            t0 = time.perf_counter()
            bufs, tok, pos, nxt = self._step(
                self.params(), self.cache.bufs, jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(live_np))
            nxt = np.asarray(nxt)
            dt = time.perf_counter() - t0
            self.cache.bufs = bufs
            self._tok = np.array(tok)      # copy: host state stays writable
            self._pos = np.array(pos)
            self.steps += 1
            self._last_progress[live_np] = self.ticks
            n_live = int(live_np.sum())
            self.metrics.observe_decode(dt, n_live)
            for slot in np.nonzero(live_np)[0]:
                req = self._req_of_slot[slot]
                req.tokens.append(int(nxt[slot]))
                self._remaining[slot] -= 1
                if self._remaining[slot] <= 0:
                    self._finish(slot)
            if on_step is not None:
                on_step(self, self.steps)
        return self.done
