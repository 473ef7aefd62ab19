"""Plain numpy reference of the serving engine's schedule.

What a continuous-batching engine with CBP decides, driven by the request
lengths alone (no model, no end-of-sequence token): which request each
slot serves at each decode step, the per-stream deficit admission over
the slot shares, the per-stream queue wait in steps, and every
``reconfig_every_steps`` steps the three CBP knobs in the paper's order:
the KV-page partition (Lookahead over each stream's stack-distance
curve), the slot shares (Algorithm 1 over the queue wait) and KV-page
readahead (Algorithm 2 on the demand hit rate).

The page accounting is the engine's documented coarse model: a page
touch's stack distance is ``active * (1 + readahead) - 1`` pages of the
same stream, a page crossing is cold unless readahead pulled the page in,
and a touch hits iff its distance is under the stream's partition.  The
engine keeps this state in float32; so does the reference, so that the
counts and the queue wait can be compared exactly.

It imports nothing of the program: the Lookahead, Algorithm 1 and
Algorithm 2 are the copies in ``reference.cmp``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Sequence

import numpy as np

from reference.cmp.bandwidth_controller import allocate_bandwidth
from reference.cmp.cache_controller import lookahead_allocate
from reference.cmp.prefetch_controller import throttle_decision

F32 = np.float32


@dataclasses.dataclass(frozen=True)
class Engine:
    """The engine's settings that the schedule depends on."""

    n_streams: int
    batch_slots: int
    max_len: int
    page_tokens: int
    total_pages: int
    reconfig_every_steps: int
    speedup_threshold: float = 1.05
    min_slot_share: float = 0.5
    min_pages: int = 2


@dataclasses.dataclass
class Schedule:
    """One ``run`` of the engine over a request list."""

    steps: int
    reconfigs: int
    partition: np.ndarray        # (n,) pages
    slot_share: np.ndarray       # (n,) slots
    queue_wait: np.ndarray       # (n,) steps, decayed at each reconfigure
    tokens_done: np.ndarray      # (n,) slot-steps
    demand_hit_rate: np.ndarray  # (n,)
    generated: np.ndarray        # (R,) tokens generated; -1: never admitted
    position_steps: np.ndarray   # (max_len,) slot-steps at each position


def run(lengths: Sequence[tuple], eng: Engine, max_steps: int) -> Schedule:
    """The schedule of one call.  ``lengths`` lists ``(stream,
    prompt_len, max_new_tokens)`` per request, in queue order."""
    n, S, U = eng.n_streams, eng.batch_slots, eng.total_pages
    R = len(lengths)
    stream = np.array([x[0] for x in lengths], np.int64)
    plen = np.array([x[1] for x in lengths], np.int64)
    max_new = np.array([x[2] for x in lengths], np.int64)

    part = np.full(n, U // n, np.int64)
    part[: U - int(part.sum())] += 1
    share = np.full(n, S / n, F32)
    readahead = np.zeros(n, bool)
    queue_wait = np.zeros(n, F32)
    last_rates = np.zeros(n, F32)
    hist = np.zeros((n, U + 1), F32)
    dhit, dmiss, occ = (np.zeros(n, np.int64) for _ in range(3))
    tokens_done = np.zeros(n, np.int64)
    queues = [deque(np.flatnonzero(stream == s).tolist()) for s in range(n)]

    active = np.zeros(S, bool)
    slot_req = np.zeros(S, np.int64)
    slot_stream = np.zeros(S, np.int64)
    pos = np.zeros(S, np.int64)
    n_gen = np.full(R, -1, np.int64)
    stream_active = np.zeros(n, np.int64)
    position_steps = np.zeros(eng.max_len, np.int64)
    steps = reconfigs = 0

    def count(mask, idx):
        return np.bincount(idx[mask], minlength=n)

    def admit():
        while not active.all():
            pending = np.array([len(q) > 0 for q in queues])
            if not pending.any():
                return
            deficit = np.where(pending, share - stream_active.astype(F32),
                               -np.inf)
            s = int(np.argmax(deficit))          # lowest stream on ties
            r = queues[s].popleft()               # FIFO within the stream
            i = int(np.argmax(~active))           # lowest empty slot
            active[i], slot_req[i], slot_stream[i], pos[i] = True, r, s, 0
            n_gen[r] = 0
            stream_active[s] += 1
            queue_wait[s] = F32(queue_wait[s] + F32(steps))

    def step():
        nonlocal steps
        upd = active.copy()
        st = slot_stream
        ra = readahead[st]
        new_page = pos % eng.page_tokens == 0
        d_re = stream_active[st] * (1 + ra.astype(np.int64)) - 1
        cold = (pos == 0) | (new_page & ~ra)
        dist = np.where(cold, U, np.minimum(d_re, U))
        hit = upd & ~cold & (dist < part[st])
        miss = upd & ~hit
        np.add.at(hist, (st[upd], dist[upd]), F32(1))
        pf = upd & ra
        pf_miss = pf & ~(~new_page & (d_re < part[st]))
        np.add.at(hist, (st[pf], np.where(new_page, U,
                                          np.minimum(d_re, U))[pf]), F32(1))
        dhit[:] += count(hit, st)
        dmiss[:] += count(miss, st)
        occ[:] += count(miss, st) + count(pf_miss, st)
        occ[:] -= np.maximum(occ - part, 0)
        tokens_done[:] += count(upd, st)
        np.add.at(position_steps, pos[upd], 1)

        p1 = pos + 1
        gen = upd & (p1 >= plen[slot_req])
        n_gen[slot_req[gen]] += 1
        done = upd & ((n_gen[slot_req] >= max_new[slot_req])
                      | (p1 >= eng.max_len - 1))
        pos[upd] = p1[upd]
        active[done] = False
        stream_active[:] -= count(done, st)
        admit()
        steps += 1

    def reconfigure():
        nonlocal part, share, readahead, last_rates, queue_wait, reconfigs
        curve = np.concatenate([np.zeros((n, 1), F32),
                                np.cumsum(hist[:, :U], axis=1, dtype=F32)],
                               axis=1)
        part = lookahead_allocate(curve, U, eng.min_pages)
        hist[:] *= F32(0.5)
        occ[:] -= np.maximum(occ - part, 0)
        share = allocate_bandwidth(queue_wait + F32(1e-6), float(S),
                                   eng.min_slot_share).astype(F32)
        queue_wait = queue_wait * F32(0.5)
        tot = dhit + dmiss
        rates = np.where(tot > 0, dhit.astype(F32)
                         / np.maximum(tot, 1).astype(F32), F32(0))
        base = rates if reconfigs == 0 else last_rates
        readahead = throttle_decision(rates + F32(1e-9), base + F32(1e-9),
                                      eng.speedup_threshold)
        last_rates = rates
        reconfigs += 1

    admit()
    chunk = eng.reconfig_every_steps
    for _ in range(max(1, -(-max_steps // chunk))):
        start = steps
        for _ in range(chunk):
            if active.any() and steps < max_steps:
                step()
        if steps - start == chunk:
            reconfigure()
        if not active.any():
            break

    tot = dhit + dmiss
    return Schedule(
        steps=steps, reconfigs=reconfigs, partition=part.copy(),
        slot_share=share.astype(np.float64),
        queue_wait=queue_wait.astype(np.float64),
        tokens_done=tokens_done,
        demand_hit_rate=np.where(tot > 0, dhit / np.maximum(tot, 1), 0.0),
        generated=n_gen, position_steps=position_steps)
