"""Device-resident continuous-batching serving engine with in-trace CBP.

:class:`JitServingEngine` rebuilds :class:`repro.serving.engine.
ServingEngine`'s per-token host loop as ONE jitted program per
reconfiguration interval: a ``lax.scan`` over decode steps with donated KV
buffers, a device-side pending-request queue, in-trace slot release and
token-bucket admission, and the three CBP knobs applied in-trace at
``reconfig_every_steps`` boundaries by reusing the traced controllers
(``lookahead_traced``, ``allocate_bandwidth_jax``,
``throttle_decision_jax``).  Between reconfigurations there are ZERO host
round-trips; the driver records one dispatch per interval
(:mod:`repro.core.dispatch`), well under the <= 2-per-interval budget.

Scheduling is the host engine's, op for op:

  * admission is a ``lax.while_loop`` that admits ONE request per group
    per trip — lowest-index empty slot, per-stream deficit
    ``slot_share - stream_active`` masked to pending streams, argmax with
    the lowest-stream-index tie-break, FIFO within the winning stream —
    exactly the host ``admit()``; trips amortize to (steps + admissions),
    not slots * pending;
  * queue wait is decode-steps-at-admission keyed by position in the
    request list (the host engine's step-keyed ``rid`` accounting);
  * per-slot positions go to ``decode_step`` as a vector, so tokens are
    identical to the host loop under greedy decode (pinned by
    ``tests/test_serving_jax.py``).

The paged-KV pool is ported to device arrays: the partition vector,
per-stream occupancy counters and a COARSE stack-distance histogram
carried through the scan (the way ``timeline_jax`` carries ATD weights).
Coarse model: a re-touched page's stack distance is the same-stream pages
touched since its last touch, ``active * (1 + readahead) - 1``; a page
crossing is cold unless readahead already pulled the page in; a touch
hits iff its distance < the stream's partition.  It feeds the same
Algorithm-2 demand-vs-prefetch split as the host pool, but is a proxy,
not a bit-mirror, of the LRU stack (tokens and scheduling do not depend
on it).

Recurrent state (the ``ssm`` and ``hybrid`` families): the model's
decode step zeroes the conv window and SSM state of every row at position
0, inside that step, so a slot that takes a new request never starts from
the last request's state.  The recurrent state is a fixed charge per slot,
outside the page partition, which covers K/V tokens only.

After ``run()`` the engine keeps what the last step left in the slots:
``cache`` (the model's cache, freed when the next run starts), and per slot
``slot_request`` (the index of its request in the run's list, -1 where the
slot is empty) and ``slot_pos`` (the tokens its request has been fed).

Scaling: ``n_groups`` splits streams/slots/pages into independent engine
shards laid out on a 2-D grid and sharded with
:func:`repro.distributed.shard_grid`; the KV cache shards its slot axis
(axis 1 of every cache leaf) in place via per-leaf PartitionSpecs, no
transposes.  Grouping is static, so results are device-count invariant;
``n_groups=1`` is bit-identical to the host engine's schedule.  The
encoder-decoder family is unsupported (its cache carries a batchless
``enc_len`` leaf).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bandwidth_controller import (
    allocate_bandwidth_jax,
    check_bandwidth_floor,
)
from repro.core.cache_controller_jax import lookahead_traced
from repro.core.dispatch import record_dispatch
from repro.core.prefetch_controller import throttle_decision_jax
from repro.distributed import PartitionSpec, shard_grid
from repro.models.model import Model
from repro.serving.engine import EngineConfig, Request

# Reconfiguration cadences above this run CBP-off: the scan chunk is capped
# and the in-trace reconfigure is compiled out (the --no-cbp baselines use
# reconfig_every_steps=10**9, which would otherwise ask for a 10**9-step
# scan).
_CHUNK_CAP = 1024
_OFF_CHUNK = 64


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _plan_grid(n_groups: int) -> Tuple[int, int, int, int]:
    """Arrange ``n_groups`` on a (K, M) grid sharded (a, b) ways.

    Mirrors :func:`repro.distributed.grid_shard_counts`' preference —
    among plans using the most devices, the most balanced mesh wins —
    but constrains shard counts to divisors so groups never pad: a | K,
    b | M, K * M == n_groups.  (1, 1) shards mean "skip shard_map".
    """
    d = jax.device_count()
    best, best_key = (n_groups, 1, 1, 1), (1, 1)
    for K in _divisors(n_groups):
        M = n_groups // K
        for a in _divisors(K):
            if a > d:
                continue
            b = max(x for x in _divisors(M) if x <= d // a)
            key = (a * b, min(a, b))
            if key > best_key:
                best_key, best = key, (K, M, a, b)
    return best


class JitServingEngine:
    """Continuous batching + CBP as one jitted interval program.

    Same constructor surface as the host :class:`ServingEngine` plus
    ``n_groups`` (independent engine shards; streams, slots and pages must
    divide evenly).  ``run()`` launches one donated device program per
    reconfiguration interval and fetches a single "any slot still active"
    scalar between intervals.
    """

    def __init__(self, model: Model, params, n_streams: int,
                 cfg: Optional[EngineConfig] = None, n_groups: int = 1,
                 min_pages: int = 2):
        self.model = model
        self.params = params
        self.cfg = cfg or EngineConfig()
        self.n_streams = n_streams
        if model.cfg.family == "encdec":
            raise ValueError("encdec caches carry a batchless enc_len leaf; "
                             "use the host ServingEngine")
        for name in ("n_streams", "batch_slots", "total_pages"):
            val = n_streams if name == "n_streams" else getattr(self.cfg,
                                                               name)
            if val % n_groups:
                raise ValueError(f"{name}={val} not divisible by "
                                 f"n_groups={n_groups}")
        self.n_groups = n_groups
        self._spg = self.cfg.batch_slots // n_groups       # slots/group
        self._npg = n_streams // n_groups                  # streams/group
        self._pages_pg = self.cfg.total_pages // n_groups  # pages/group
        self._min_pages = min_pages
        if min_pages * self._npg > self._pages_pg:
            raise ValueError("pool too small for min_pages floor")
        check_bandwidth_floor(self.cfg.min_slot_share, self._npg,
                              float(self._spg))
        self._cbp_on = self.cfg.reconfig_every_steps <= _CHUNK_CAP
        self._chunk = (self.cfg.reconfig_every_steps if self._cbp_on
                       else _OFF_CHUNK)
        self._grid = _plan_grid(n_groups)
        self._interval_jit = jax.jit(self._interval, donate_argnums=(0,))
        # filled by run():
        self.steps = 0
        self.reconfigs = 0
        self.intervals = 0
        self.cache = None

    # ------------------------------------------------------------- #
    # state construction (host side, once per run)
    # ------------------------------------------------------------- #

    def _build_state(self, requests: List[Request]) -> Dict:
        G, spg, npg = self.n_groups, self._spg, self._npg
        cfgE = self.cfg
        per_group: List[List[int]] = [[] for _ in range(G)]
        for i, r in enumerate(requests):
            if not (0 <= r.stream < self.n_streams):
                raise ValueError(f"request stream {r.stream} out of range")
            if len(r.prompt) < 1:
                raise ValueError("empty prompt")
            r.rid = i
            per_group[r.stream // npg].append(i)
        R = max(1, max(len(g) for g in per_group))
        P = max(1, max((len(r.prompt) for r in requests), default=1))
        C = max(1, max((r.max_new_tokens for r in requests), default=1))
        self._req_loc = {}

        prompts = np.zeros((G, R, P), dtype=np.int32)
        prompt_len = np.ones((G, R), dtype=np.int32)
        req_stream = np.zeros((G, R), dtype=np.int32)
        max_new = np.zeros((G, R), dtype=np.int32)
        admitted = np.ones((G, R), dtype=bool)   # padding pre-admitted
        done = np.ones((G, R), dtype=bool)       # ... and pre-done
        enqueue_step = np.zeros((G, R), dtype=np.int32)
        pend_count = np.zeros((G, npg), dtype=np.int32)
        for g, idxs in enumerate(per_group):
            for r_loc, i in enumerate(idxs):
                req = requests[i]
                self._req_loc[i] = (g, r_loc)
                p = np.asarray(req.prompt, dtype=np.int32)
                prompts[g, r_loc, : len(p)] = p
                prompt_len[g, r_loc] = len(p)
                req_stream[g, r_loc] = req.stream % npg
                max_new[g, r_loc] = req.max_new_tokens
                admitted[g, r_loc] = False
                done[g, r_loc] = False
                pend_count[g, req.stream % npg] += 1

        U = self._pages_pg
        part = np.full((G, npg), U // npg, dtype=np.int32)
        part[:, : U - int(part[0].sum())] += 1
        q = {
            "tokens": np.zeros((G, spg), dtype=np.int32),
            "pos": np.zeros((G, spg), dtype=np.int32),
            "active": np.zeros((G, spg), dtype=bool),
            "slot_req": np.zeros((G, spg), dtype=np.int32),
            "slot_stream": np.zeros((G, spg), dtype=np.int32),
            "steps": np.zeros((G,), dtype=np.int32),
            "prompts": prompts, "prompt_len": prompt_len,
            "req_stream": req_stream, "max_new": max_new,
            "admitted": admitted, "done": done,
            "enqueue_step": enqueue_step, "pend_count": pend_count,
            "out_tokens": np.zeros((G, R, C), dtype=np.int32),
            "n_gen": np.zeros((G, R), dtype=np.int32),
            "partition": part,
            "slot_share": np.full((G, npg), spg / npg, dtype=np.float32),
            "readahead": np.zeros((G, npg), dtype=bool),
            "queue_wait": np.zeros((G, npg), dtype=np.float32),
            "stream_active": np.zeros((G, npg), dtype=np.int32),
            "sd_hist": np.zeros((G, npg, U + 1), dtype=np.float32),
            "demand_hits": np.zeros((G, npg), dtype=np.int32),
            "demand_misses": np.zeros((G, npg), dtype=np.int32),
            "prefetch_hits": np.zeros((G, npg), dtype=np.int32),
            "prefetch_misses": np.zeros((G, npg), dtype=np.int32),
            "occupancy": np.zeros((G, npg), dtype=np.int32),
            "evictions": np.zeros((G, npg), dtype=np.int32),
            "tokens_done": np.zeros((G, npg), dtype=np.int32),
            "last_rates": np.zeros((G, npg), dtype=np.float32),
            "reconfigs": np.zeros((G,), dtype=np.int32),
            "nonfinite_logits": np.zeros((G,), dtype=np.int32),
        }
        self._prime(q)
        kv = self.model.init_cache(
            G * spg, cfgE.max_len,
            dtype=jnp.dtype(self.model.cfg.kv_cache_dtype))
        S = G * spg
        for leaf in jax.tree.leaves(kv):
            if leaf.ndim < 2 or leaf.shape[1] != S:
                raise ValueError(
                    "cache leaf without a slot axis at position 1: "
                    f"shape {leaf.shape} (family {self.model.cfg.family})")
        return {"kv": kv,
                "q": {k: jnp.asarray(v) for k, v in q.items()}}

    def _prime(self, q: Dict) -> None:
        """Initial admission, host-side numpy: the exact in-trace pick
        (lowest empty slot; deficit argmax over pending streams, lowest
        stream index on ties; FIFO within the stream) — saves one device
        dispatch before the first interval."""
        G, spg = q["active"].shape
        for g in range(G):
            for i in range(spg):
                if not q["pend_count"][g].sum():
                    break
                deficit = (q["slot_share"][g]
                           - q["stream_active"][g].astype(np.float32))
                deficit = np.where(q["pend_count"][g] > 0, deficit, -np.inf)
                s = int(np.argmax(deficit))
                cand = (~q["admitted"][g] & ~q["done"][g]
                        & (q["req_stream"][g] == s))
                r = int(np.argmax(cand))
                q["admitted"][g, r] = True
                q["active"][g, i] = True
                q["slot_req"][g, i] = r
                q["slot_stream"][g, i] = s
                q["tokens"][g, i] = q["prompts"][g, r, 0]
                q["pos"][g, i] = 0
                q["stream_active"][g, s] += 1
                q["pend_count"][g, s] -= 1
                q["queue_wait"][g, s] += float(
                    q["steps"][g] - q["enqueue_step"][g, r])

    # ------------------------------------------------------------- #
    # traced interval program
    # ------------------------------------------------------------- #

    def _one_step(self, st: Dict, params, max_steps) -> Dict:
        cfgE = self.cfg
        q = st["q"]
        G, spg = q["active"].shape
        R = q["admitted"].shape[1]
        P = q["prompts"].shape[2]
        U = self._pages_pg
        f32 = jnp.float32
        gi = jnp.arange(G, dtype=jnp.int32)
        gi2 = jnp.broadcast_to(gi[:, None], (G, spg))
        live = q["active"].any(-1) & (q["steps"] < max_steps)   # (G,)
        upd = q["active"] & live[:, None]                       # (G, spg)

        # ---- decode every slot at ITS position (satellite: vector pos) --
        logits, kv = self.model.decode_step(
            params, st["kv"], q["tokens"].reshape(G * spg, 1),
            q["pos"].reshape(G * spg))
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)
        nxt = nxt.astype(jnp.int32).reshape(G, spg)
        bad = ~jnp.isfinite(logits[:, -1, :]).all(-1).reshape(G, spg) & upd

        # ---- coarse paged-KV accounting at the current position ---------
        strm = q["slot_stream"]
        ra = jnp.take_along_axis(q["readahead"], strm, 1)
        acnt = jnp.take_along_axis(q["stream_active"], strm, 1)
        part = jnp.take_along_axis(q["partition"], strm, 1)
        new_page = (q["pos"] % cfgE.page_tokens) == 0
        d_re = acnt * (1 + ra.astype(jnp.int32)) - 1
        cold = (q["pos"] == 0) | (new_page & ~ra)
        dist = jnp.where(cold, U, jnp.minimum(d_re, U))
        hit = upd & ~cold & (dist < part)
        miss = upd & ~hit
        sd_hist = q["sd_hist"].at[gi2, strm, dist].add(upd.astype(f32))
        # readahead touch of (page + 1): first touch per page is a cold
        # insert, later touches re-touch at the same coarse distance.
        pf = upd & ra
        pf_hit = pf & ~new_page & (d_re < part)
        pf_miss = pf & ~pf_hit
        pf_idx = jnp.where(new_page, U, jnp.minimum(d_re, U))
        sd_hist = sd_hist.at[gi2, strm, pf_idx].add(pf.astype(f32))
        demand_hits = q["demand_hits"].at[gi2, strm].add(
            hit.astype(jnp.int32))
        demand_misses = q["demand_misses"].at[gi2, strm].add(
            miss.astype(jnp.int32))
        prefetch_hits = q["prefetch_hits"].at[gi2, strm].add(
            pf_hit.astype(jnp.int32))
        prefetch_misses = q["prefetch_misses"].at[gi2, strm].add(
            pf_miss.astype(jnp.int32))
        occupancy = q["occupancy"].at[gi2, strm].add(
            miss.astype(jnp.int32) + pf_miss.astype(jnp.int32))
        over = jnp.maximum(occupancy - q["partition"], 0)  # LRU enforcement
        evictions = q["evictions"] + over
        occupancy = occupancy - over
        tokens_done = q["tokens_done"].at[gi2, strm].add(
            upd.astype(jnp.int32))

        # ---- advance: teacher-force the prompt, emit, retire ------------
        p1 = q["pos"] + 1
        plen = jnp.take_along_axis(q["prompt_len"], q["slot_req"], 1)
        prompt_tok = q["prompts"][gi2, q["slot_req"],
                                  jnp.clip(p1, 0, P - 1)]
        in_prompt = p1 < plen
        tok_next = jnp.where(in_prompt, prompt_tok, nxt)
        gen_now = upd & ~in_prompt
        req_sel = jnp.where(gen_now, q["slot_req"], R)  # OOB rows dropped
        ci = jnp.take_along_axis(q["n_gen"], q["slot_req"], 1)
        out_tokens = q["out_tokens"].at[gi2, req_sel, ci].set(
            nxt, mode="drop")
        n_gen = q["n_gen"].at[gi2, req_sel].add(1, mode="drop")
        maxnew = jnp.take_along_axis(q["max_new"], q["slot_req"], 1)
        ng_after = ci + gen_now.astype(jnp.int32)
        done_now = upd & ((ng_after >= maxnew) | (p1 >= cfgE.max_len - 1))
        tokens = jnp.where(upd, tok_next, q["tokens"])
        pos = jnp.where(upd, p1, q["pos"])
        active = q["active"] & ~done_now
        stream_active = q["stream_active"].at[gi2, strm].add(
            -done_now.astype(jnp.int32))
        done = q["done"].at[gi2, jnp.where(done_now, q["slot_req"], R)].set(
            True, mode="drop")

        # ---- admission: one request per group per while trip ------------
        def adm_cond(c):
            c_active = c[0]
            c_pend = c[6]
            return ((live & (~c_active).any(-1)
                     & (c_pend.sum(-1) > 0)).any())

        def adm_body(c):
            (c_active, c_sreq, c_sstrm, c_pos, c_tok, c_sact, c_pend,
             c_qw, c_adm) = c
            empty = ~c_active
            slot_i = jnp.argmax(empty, -1).astype(jnp.int32)      # (G,)
            deficit = q["slot_share"] - c_sact.astype(f32)
            deficit = jnp.where(c_pend > 0, deficit, -jnp.inf)
            s = jnp.argmax(deficit, -1).astype(jnp.int32)         # (G,)
            can = live & empty.any(-1) & (c_pend.sum(-1) > 0)
            cand = ~c_adm & ~done & (q["req_stream"] == s[:, None])
            r = jnp.argmax(cand, -1).astype(jnp.int32)            # FIFO
            can = can & cand.any(-1)
            rsel = jnp.where(can, r, R)
            ssel = jnp.where(can, slot_i, spg)
            c_adm = c_adm.at[gi, rsel].set(True, mode="drop")
            c_active = c_active.at[gi, ssel].set(True, mode="drop")
            c_sreq = c_sreq.at[gi, ssel].set(r, mode="drop")
            c_sstrm = c_sstrm.at[gi, ssel].set(s, mode="drop")
            c_pos = c_pos.at[gi, ssel].set(0, mode="drop")
            tok0 = q["prompts"][gi, jnp.clip(r, 0, R - 1), 0]
            c_tok = c_tok.at[gi, ssel].set(tok0, mode="drop")
            inc = can.astype(jnp.int32)
            c_sact = c_sact.at[gi, s].add(inc)
            c_pend = c_pend.at[gi, s].add(-inc)
            enq = q["enqueue_step"][gi, jnp.clip(r, 0, R - 1)]
            wait = jnp.where(can, (q["steps"] - enq).astype(f32), 0.0)
            c_qw = c_qw.at[gi, s].add(wait)
            return (c_active, c_sreq, c_sstrm, c_pos, c_tok, c_sact,
                    c_pend, c_qw, c_adm)

        def adm_quad(c):
            # Four admissions per while trip: once nothing is admittable
            # the body is a no-op (`can` gates every scatter to dropped
            # indices and zero adds), so the unroll preserves the exact
            # one-at-a-time deficit schedule while quartering the
            # while_loop's per-trip overhead — the same trick as
            # ``cache_controller_jax._greedy_loop``'s body_quad, and for
            # the same reason: on CPU the trips are tiny-op bound.
            return adm_body(adm_body(adm_body(adm_body(c))))

        (active, slot_req, slot_stream, pos, tokens, stream_active,
         pend_count, queue_wait, admitted) = jax.lax.while_loop(
            adm_cond, adm_quad,
            (active, q["slot_req"], q["slot_stream"], pos, tokens,
             stream_active, q["pend_count"], q["queue_wait"],
             q["admitted"]))

        q2 = dict(
            q, tokens=tokens, pos=pos, active=active, slot_req=slot_req,
            slot_stream=slot_stream, steps=q["steps"] + live.astype(
                jnp.int32),
            admitted=admitted, done=done, pend_count=pend_count,
            out_tokens=out_tokens, n_gen=n_gen, sd_hist=sd_hist,
            demand_hits=demand_hits, demand_misses=demand_misses,
            prefetch_hits=prefetch_hits, prefetch_misses=prefetch_misses,
            occupancy=occupancy, evictions=evictions,
            stream_active=stream_active, queue_wait=queue_wait,
            tokens_done=tokens_done,
            nonfinite_logits=q["nonfinite_logits"] + bad.sum(-1))
        return {"kv": kv, "q": q2}

    def _reconfigure(self, st: Dict, did_full) -> Dict:
        """Cache -> bandwidth -> prefetch, the paper's priority order,
        gated per group on having advanced a full interval."""
        q = st["q"]
        G, n = q["partition"].shape
        U = self._pages_pg
        f32 = jnp.float32
        a1 = did_full[:, None]
        # 1. cache: UCP/Lookahead over the coarse stack-distance curves
        # (curve[0] = 0; curve[k] = hits with k pages = cumsum of the
        # finite-distance histogram — StackDistanceMonitor.utility_curve).
        hist = q["sd_hist"]
        curve = jnp.concatenate(
            [jnp.zeros((G, n, 1), f32),
             jnp.cumsum(hist[..., :U], axis=-1)], axis=-1)
        part_new = lookahead_traced(
            curve, jnp.full((G,), self._min_pages, jnp.int32),
            total_units=U, backend="jax").astype(jnp.int32)
        partition = jnp.where(a1, part_new, q["partition"])
        sd_hist = jnp.where(did_full[:, None, None], hist * 0.5, hist)
        over = jnp.where(a1, jnp.maximum(q["occupancy"] - partition, 0), 0)
        evictions = q["evictions"] + over
        occupancy = q["occupancy"] - over
        # 2. bandwidth: Algorithm 1 over accumulated queue wait
        share_new = allocate_bandwidth_jax(
            q["queue_wait"] + 1e-6, float(self._spg),
            self.cfg.min_slot_share).astype(f32)
        slot_share = jnp.where(a1, share_new, q["slot_share"])
        queue_wait = jnp.where(a1, q["queue_wait"] * 0.5, q["queue_wait"])
        # 3. prefetch: Algorithm 2 on the DEMAND hit-rate gain
        tot = q["demand_hits"] + q["demand_misses"]
        rates = jnp.where(tot > 0,
                          q["demand_hits"].astype(f32)
                          / jnp.maximum(tot, 1).astype(f32), 0.0)
        base = jnp.where((q["reconfigs"] == 0)[:, None], rates,
                         q["last_rates"])
        ra_new = throttle_decision_jax(rates + 1e-9, base + 1e-9,
                                       self.cfg.speedup_threshold)
        readahead = jnp.where(a1, ra_new, q["readahead"])
        last_rates = jnp.where(a1, rates, q["last_rates"])
        q2 = dict(q, partition=partition, sd_hist=sd_hist,
                  evictions=evictions, occupancy=occupancy,
                  slot_share=slot_share, queue_wait=queue_wait,
                  readahead=readahead, last_rates=last_rates,
                  reconfigs=q["reconfigs"] + did_full.astype(jnp.int32))
        return {"kv": st["kv"], "q": q2}

    def _group_body(self, st: Dict, params, max_steps) -> Dict:
        start = st["q"]["steps"]

        def step(s, _):
            # Skip the decode entirely once every group is frozen (all
            # done or at max_steps): the scan length is static, so the
            # tail of the final interval would otherwise burn full decode
            # steps on a dead batch.
            any_live = jnp.any(s["q"]["active"].any(-1)
                               & (s["q"]["steps"] < max_steps))
            return jax.lax.cond(
                any_live, lambda x: self._one_step(x, params, max_steps),
                lambda x: x, s), None

        st, _ = jax.lax.scan(step, st, None, length=self._chunk)
        if self._cbp_on:
            # Freezing (all-done / max_steps) is permanent, so a group
            # either advanced the whole interval or never will again.
            st = self._reconfigure(st, (st["q"]["steps"] - start)
                                   == self._chunk)
        return st

    def _interval(self, state: Dict, params, max_steps):
        K, M, a, b = self._grid
        if a * b == 1:
            st = self._group_body(state, params, max_steps)
        else:
            spg = self._spg

            def to_grid(s):
                return {
                    "kv": jax.tree.map(
                        lambda l: l.reshape(l.shape[:1] + (K, M, spg)
                                            + l.shape[2:]), s["kv"]),
                    "q": jax.tree.map(
                        lambda l: l.reshape((K, M) + l.shape[1:]), s["q"]),
                }

            def from_grid(s):
                return {
                    "kv": jax.tree.map(
                        lambda l: l.reshape(l.shape[:1] + (K * M * spg,)
                                            + l.shape[4:]), s["kv"]),
                    "q": jax.tree.map(
                        lambda l: l.reshape((K * M,) + l.shape[2:]),
                        s["q"]),
                }

            def worker(grid, _gids, repl):
                p, ms = repl
                Kl = grid["q"]["steps"].shape[0]
                Ml = grid["q"]["steps"].shape[1]
                loc = {
                    "kv": jax.tree.map(
                        lambda l: l.reshape(l.shape[:1] + (Kl * Ml * spg,)
                                            + l.shape[4:]), grid["kv"]),
                    "q": jax.tree.map(
                        lambda l: l.reshape((Kl * Ml,) + l.shape[2:]),
                        grid["q"]),
                }
                out = self._group_body(loc, p, ms)
                return {
                    "kv": jax.tree.map(
                        lambda l: l.reshape(l.shape[:1] + (Kl, Ml, spg)
                                            + l.shape[2:]), out["kv"]),
                    "q": jax.tree.map(
                        lambda l: l.reshape((Kl, Ml) + l.shape[1:]),
                        out["q"]),
                }

            g, r = "sg", "sr"
            grid_specs = {
                # cache leaves: slot axis lives at position 1 — shard the
                # (K, M) split of that axis in place, layer axis untouched.
                "kv": PartitionSpec(None, g, r),
                "q": PartitionSpec(g, r),
            }
            st = from_grid(shard_grid(
                worker, (a, b), (g, r), grid_specs=grid_specs)(
                    to_grid(state), jnp.arange(K), (params, max_steps)))
        return st, st["q"]["active"].any()

    # ------------------------------------------------------------- #
    # driver
    # ------------------------------------------------------------- #

    def run(self, requests: List[Request], max_steps: int = 10_000
            ) -> List[Request]:
        """Continuous batching over the request list; one device dispatch
        per reconfiguration interval."""
        if not requests:
            return requests
        self.cache = None           # the last run's, freed before this one's
        state = self._build_state(requests)
        ms = jnp.int32(min(max_steps, np.iinfo(np.int32).max))
        n_intervals = max(1, math.ceil(max_steps / self._chunk))
        self.intervals = 0
        for _ in range(n_intervals):
            record_dispatch()
            state, any_active = self._interval_jit(state, self.params, ms)
            self.intervals += 1
            if not bool(any_active):
                break
        self._finalize(state, requests)
        return requests

    def _finalize(self, state: Dict, requests: List[Request]) -> None:
        q = {k: np.asarray(v) for k, v in state["q"].items()}
        for i, req in enumerate(requests):
            g, r = self._req_loc[i]
            if q["admitted"][g, r]:
                k = int(q["n_gen"][g, r])
                req.generated = [int(t) for t in q["out_tokens"][g, r, :k]]

        def flat(name):
            return q[name].reshape(-1)  # stream s = g * npg + s_local

        self.nonfinite_logits = int(q["nonfinite_logits"].sum())
        self.cache = state["kv"]
        req_at = {loc: i for i, loc in self._req_loc.items()}
        self.slot_request = np.array(
            [req_at[(g, int(r))] if a else -1 for g in range(len(q["active"]))
             for r, a in zip(q["slot_req"][g], q["active"][g])])
        self.slot_pos = flat("pos").astype(np.int64)
        self.steps = int(q["steps"].max())
        self.reconfigs = int(q["reconfigs"].max())
        self.slot_share = flat("slot_share").astype(np.float64)
        self.queue_wait = flat("queue_wait").astype(np.float64)
        self.readahead = flat("readahead")
        self.partition = flat("partition").astype(np.int64)
        self.occupancy = flat("occupancy").astype(np.int64)
        self.evictions = flat("evictions").astype(np.int64)
        self.tokens_done = flat("tokens_done").astype(np.float64)
        hits, misses = flat("demand_hits"), flat("demand_misses")
        tot = np.maximum(hits + misses, 1)
        self.demand_hit_rate = np.where(hits + misses > 0,
                                        hits / tot, 0.0)
        ph, pm = flat("prefetch_hits"), flat("prefetch_misses")
        self.prefetch_hit_rate = np.where(ph + pm > 0,
                                          ph / np.maximum(ph + pm, 1), 0.0)
