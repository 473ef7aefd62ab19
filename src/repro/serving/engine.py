"""Batched serving engine with full CBP coordination (host reference).

The engine runs greedy decode over a fixed slot batch (continuous batching:
finished requests release their slot to the queue) and binds all three CBP
knobs:

  * cache      — the :class:`PagedKVPool` partitions KV pages across
    request streams (UCP over stack-distance curves);
  * bandwidth  — per-stream token-bucket admission: each stream's share of
    decode slots is allocated proportionally to its measured queue wait
    (Algorithm 1, units = slots/interval instead of GB/s);
  * prefetch   — KV-page readahead per stream, A/B sampled and throttled
    by the measured DEMAND hit-rate speedup (Algorithm 2; readahead
    touches are tagged prefetch in the pool so they cannot inflate their
    own A/B signal).

This host loop is the golden reference for the device-resident engine
(:mod:`repro.serving.engine_jax`): everything that decides tokens or
scheduling is deterministic —

  * per-slot positions travel to ``decode_step`` as a VECTOR, so a newly
    admitted slot decodes at ITS position 0 while its neighbours sit
    mid-sequence (a scalar ``pos.max()`` used to make staggered
    admissions write/attend at the wrong cache rows);
  * queue wait is accounted in decode STEPS keyed by an engine-assigned
    request id (wall-clock timestamps made Algorithm 1 nondeterministic,
    and ``t_in if t_in else ...`` misfired on the falsy-but-valid zeroth
    tick and on re-admission);
  * a slot that takes a new request decodes it from position 0, where
    the model's decode step zeroes any recurrent state (conv window and
    SSM state of the ``ssm`` and ``hybrid`` families) of that row;
  * the token-bucket admission pick is a per-STREAM deficit argmax with a
    lowest-stream-index tie-break, then FIFO within the winning stream
    (was a first-come scan over the pending list, i.e. the tie-break
    depended on interleaving).

On-CPU tests drive it with tiny models; the decode step is
``model.decode_step`` in its per-row form, jitted with the cache donated
so that each step writes its rows into the one cache in place.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bandwidth_controller import (
    allocate_bandwidth,
    check_bandwidth_floor,
)
from repro.core.prefetch_controller import throttle_decision
from repro.models.model import Model
from repro.serving.kv_cache import PagedKVPool


@dataclasses.dataclass
class Request:
    stream: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    # filled in by the engine:
    generated: Optional[List[int]] = None
    slot: int = -1
    pages_touched: int = 0
    rid: int = -1                      # engine-assigned id; stable across
    #                                    re-admission (id(req) is not)


@dataclasses.dataclass
class EngineConfig:
    batch_slots: int = 4
    max_len: int = 128
    page_tokens: int = 16              # tokens per KV page
    total_pages: int = 64
    reconfig_every_steps: int = 32     # CBP reconfiguration interval
    speedup_threshold: float = 1.05
    min_slot_share: float = 0.5


class ServingEngine:
    def __init__(self, model: Model, params, n_streams: int,
                 cfg: Optional[EngineConfig] = None):
        self.model = model
        self.params = params
        self.cfg = cfg or EngineConfig()
        self.n_streams = n_streams
        check_bandwidth_floor(self.cfg.min_slot_share, n_streams,
                              float(self.cfg.batch_slots))
        self.pool = PagedKVPool(self.cfg.total_pages, n_streams)
        self.kv = model.init_cache(self.cfg.batch_slots, self.cfg.max_len,
                                   dtype=jnp.dtype(model.cfg.kv_cache_dtype))
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        # CBP state
        self.slot_share = np.full(n_streams,
                                  self.cfg.batch_slots / n_streams)
        self.readahead = np.zeros(n_streams, dtype=bool)
        self.queue_wait = np.zeros(n_streams)
        self.tokens_done = np.zeros(n_streams)
        self.steps = 0
        self.reconfigs = 0
        self._next_rid = 0

    # ------------------------------------------------------------- #

    def _touch_pages(self, req: Request, pos: int) -> None:
        page = pos // self.cfg.page_tokens
        self.pool.access(req.stream, (req.stream, req.rid, page))
        if self.readahead[req.stream]:
            self.pool.access(req.stream, (req.stream, req.rid, page + 1),
                             prefetch=True)
        req.pages_touched += 1

    def run(self, requests: List[Request], max_steps: int = 10_000
            ) -> List[Request]:
        """Continuous batching over the request list."""
        cfgE = self.cfg
        pending: List[Request] = list(requests)
        active: List[Optional[Request]] = [None] * cfgE.batch_slots
        tokens = np.zeros((cfgE.batch_slots, 1), dtype=np.int32)
        pos = np.zeros(cfgE.batch_slots, dtype=np.int64)
        enqueue_step: Dict[int, int] = {}
        stream_active = np.zeros(self.n_streams)

        def admit():
            for i in range(cfgE.batch_slots):
                if active[i] is not None:
                    continue
                if not pending:
                    break
                # token-bucket: the pending STREAM most under its slot
                # share wins; exact deficit ties break to the lowest
                # stream index, then FIFO within the stream.
                deficit = self.slot_share - stream_active
                has_pending = np.zeros(self.n_streams, dtype=bool)
                for r in pending:
                    has_pending[r.stream] = True
                deficit = np.where(has_pending, deficit, -np.inf)
                s = int(np.argmax(deficit))   # first max = lowest index
                best_j = next(j for j, r in enumerate(pending)
                              if r.stream == s)
                req = pending.pop(best_j)
                req.generated = []
                req.slot = i
                active[i] = req
                stream_active[req.stream] += 1
                t_in = enqueue_step.pop(req.rid, None)
                # `is not None`: step 0 is a perfectly valid enqueue tick.
                self.queue_wait[req.stream] += (
                    self.steps - t_in if t_in is not None else 0.0)
                tokens[i, 0] = req.prompt[0]
                pos[i] = 0

        for r in pending:
            r.rid = self._next_rid
            self._next_rid += 1
            enqueue_step[r.rid] = self.steps
        admit()

        steps = 0
        while any(a is not None for a in active) and steps < max_steps:
            # Per-slot positions go down as a VECTOR: each slot writes and
            # attends at its own position (a scalar max() corrupted newly
            # admitted slots whose position had reset to 0).
            logits, self.kv = self._decode(
                self.params, self.kv, jnp.asarray(tokens),
                jnp.asarray(pos, jnp.int32))
            nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
            for i, req in enumerate(active):
                if req is None:
                    continue
                self._touch_pages(req, int(pos[i]))
                p = int(pos[i]) + 1
                if p < len(req.prompt):
                    tokens[i, 0] = req.prompt[p]      # teacher-force prompt
                else:
                    req.generated.append(int(nxt[i]))
                    tokens[i, 0] = int(nxt[i])
                pos[i] = p
                self.tokens_done[req.stream] += 1
                done = (len(req.generated) >= req.max_new_tokens
                        or p >= cfgE.max_len - 1)
                if done:
                    stream_active[req.stream] -= 1
                    active[i] = None
            admit()
            steps += 1
            self.steps += 1
            if self.steps % cfgE.reconfig_every_steps == 0:
                self._reconfigure()
        return requests

    # ---------------- CBP coordination ---------------- #

    def _reconfigure(self) -> None:
        """Priority order per the paper: cache -> bandwidth -> prefetch."""
        self.reconfigs += 1
        # 1. cache: UCP over stack-distance curves
        self.pool.reconfigure()
        # 2. bandwidth: slots proportional to queue wait (Algorithm 1)
        self.slot_share = allocate_bandwidth(
            self.queue_wait + 1e-6, float(self.cfg.batch_slots),
            self.cfg.min_slot_share)
        self.queue_wait *= 0.5  # accumulate-with-decay (paper §3.3)
        # 3. prefetch: A/B throttle readahead on per-stream DEMAND
        # hit-rate gain (tokens/sec proxy on CPU): enable readahead for
        # streams whose demand hit rate improved while it was on —
        # prefetch touches are tagged in the pool and excluded here.
        rates = np.array([s.hit_rate for s in self.pool.stats])
        base = getattr(self, "_last_rates", rates)
        self.readahead = throttle_decision(
            rates + 1e-9, base + 1e-9, self.cfg.speedup_threshold)
        self._last_rates = rates
