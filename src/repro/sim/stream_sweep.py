"""Fault-tolerant streaming sweep service: 10^5-10^6 mixes, chunked and
double-buffered, with online aggregation and checkpoint/resume.

ROADMAP item 3: consolidation decisions over millions of users mean
evaluating the Table-3 manager set over 10^5-10^6 workload mixes streamed
continuously — far past what ``run_sweep``'s materialize-all-rows shape
can hold, and far past the runtime where "nothing ever fails" is a usable
assumption.  This module is both the *scale* layer (chunked pipeline,
online aggregates, bounded memory) and the *robustness* layer (retry,
quarantine, finite guards, watchdog, atomic checkpoint/resume) over
:func:`repro.sim.timeline_jax.run_timelines`.

Pipeline
    The stream is processed in fixed-size chunks.  Chunk c's device
    program is dispatched and fetched on a single worker thread while the
    host thread generates chunk c+1's scenario arrays
    (:func:`repro.sim.workloads.scenario_chunk`) — classic double
    buffering, built on :func:`repro.sim.timeline_jax.run_timelines_async`
    so the dispatch never blocks on the transfer.  Every chunk is
    **3 recorded device programs** (stacked manager set + shared baseline
    + the metrics/finite-guard reduction, counter
    :func:`repro.core.device_dispatches`) regardless of chunk size.

Online aggregates
    Nothing materializes per-mix rows: each chunk folds into
    :class:`StreamAggregates` — running log-sum for the geomean weighted
    speedup, a fixed-bin histogram sketch for p50/p90/p99 per-app
    slowdown, running max-slowdown and min-fairness — all plain float64
    numpy, folded in chunk order, so the final aggregates of a resumed run
    are *bit-identical* to an uninterrupted one.

Robustness contract (each layer is fault-injectable via
:class:`repro.runtime.faultinject.FaultPlan`):

* chunk dispatch failures retry with exponential backoff
  (:class:`RetryPolicy`); a chunk that exhausts its retries is
  **quarantined** and the stream keeps going — the report carries an
  explicit ``coverage`` fraction and names every quarantined chunk
  (graceful degradation, never silent truncation);
* an in-trace finite guard (the metrics program reduces
  ``isfinite`` over every (manager, mix) row on device) surfaces
  :class:`NumericalDivergenceError` naming the offending (manager, mix);
  the service quarantines the chunk by default (``on_divergence="raise"``
  propagates instead);
* per-chunk walls feed a :class:`repro.runtime.fault.StragglerWatchdog`
  (median-seeded warm-up so jit compilation cannot poison the baseline);
* the full service state — aggregation sketches, chunk cursor, quarantine
  list, total retry count — checkpoints atomically through
  :class:`repro.checkpoint.CheckpointManager` every ``checkpoint_every``
  chunks; a killed run resumes from the last complete checkpoint and
  reproduces the uninterrupted run's final aggregates bit-for-bit
  (chunk generation is a pure function of ``(seed, chunk_index)`` —
  no RNG state threads between chunks, so the cursor IS the RNG state);
* ``max_consecutive_quarantines`` bounds pathological streams: a service
  that quarantines everything is broken, not degraded, and must say so.

CI: ``benchmarks/stream_bench.py --smoke`` gates the resume-parity
contract (dispatch failure + retry, NaN-poisoned chunk quarantine, mid-run
kill + resume -> bit-identical aggregates), the per-chunk dispatch budget
and the overlap-vs-serial pipeline; ``tools/stream_sweep.py`` is the CLI.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import hashlib
import json
import pathlib
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import CBPParams
from repro.core.dispatch import record_dispatch
from repro.core.x64 import x64_context
from repro.runtime.fault import StragglerWatchdog
from repro.runtime.faultinject import FaultPlan
from repro.sim import memsys_jax, timeline_jax
from repro.sim import policies
from repro.sim.managers import MANAGER_NAMES
from repro.sim.runner import equal_share
from repro.sim.workloads import StreamScenario, scenario_chunk


class NumericalDivergenceError(RuntimeError):
    """A (manager, mix) row produced a non-finite result.

    Raised off the in-trace finite guard; names the offending manager and
    the *global* mix index so a 10^6-mix stream pinpoints the row.
    """

    def __init__(self, manager: str, mix_index: int, chunk_index: int):
        self.manager = manager
        self.mix_index = mix_index
        self.chunk_index = chunk_index
        super().__init__(
            f"non-finite result for manager {manager!r}, mix {mix_index} "
            f"(chunk {chunk_index})")


class CheckpointMismatchError(RuntimeError):
    """A resume was attempted against a checkpoint of a different stream
    (different seed/shape/scenario): resuming would corrupt aggregates."""


class StreamAbortedError(RuntimeError):
    """Too many consecutive chunk quarantines — the stream is broken, not
    degraded, and refusing to continue beats silently reporting ~0
    coverage after hours of wall time."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff for chunk dispatch failures."""

    max_retries: int = 3
    backoff_s: float = 0.05
    multiplier: float = 2.0
    max_backoff_s: float = 2.0

    def delay(self, attempt: int) -> float:
        return min(self.backoff_s * self.multiplier ** attempt,
                   self.max_backoff_s)


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Shape + policy of one streaming sweep run."""

    n_mixes: int
    chunk_size: int = 512
    managers: Optional[Tuple[str, ...]] = None   # None = all MANAGER_NAMES
    total_ms: float = 50.0
    seed: int = 0
    scenario: StreamScenario = dataclasses.field(
        default_factory=StreamScenario)
    total_cache_units: int = 256
    total_bandwidth: float = 64.0
    llc_extra_cycles: float = 0.0
    params: CBPParams = dataclasses.field(default_factory=CBPParams)
    # Aggregation sketch: fixed uniform bins over [0, hist_max_slowdown)
    # plus a final overflow bin.
    hist_bins: int = 512
    hist_max_slowdown: float = 8.0
    # Robustness policy.
    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    on_divergence: str = "quarantine"            # "quarantine" | "raise"
    max_consecutive_quarantines: int = 8
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 8
    watchdog_threshold: float = 3.0
    watchdog_warmup: int = 3

    def __post_init__(self):
        if self.n_mixes < 1 or self.chunk_size < 1:
            raise ValueError("n_mixes and chunk_size must be >= 1")
        if self.on_divergence not in ("quarantine", "raise"):
            raise ValueError(
                f"unknown on_divergence {self.on_divergence!r}")
        if self.hist_bins < 2:
            raise ValueError("hist_bins must be >= 2")
        # UnknownManagerError (a ValueError) on the first unregistered name.
        policies.validate_manager_names(self.manager_names)

    @property
    def manager_names(self) -> List[str]:
        return (list(MANAGER_NAMES) if self.managers is None
                else list(self.managers))

    @property
    def n_chunks(self) -> int:
        return -(-self.n_mixes // self.chunk_size)

    def fingerprint(self) -> str:
        """Stream identity — a resumed run must match it exactly."""
        payload = {
            "n_mixes": self.n_mixes, "chunk_size": self.chunk_size,
            "managers": self.manager_names, "total_ms": self.total_ms,
            "seed": self.seed,
            "scenario": dataclasses.asdict(self.scenario),
            "caps": [self.total_cache_units, self.total_bandwidth,
                     self.llc_extra_cycles],
            "params": dataclasses.asdict(self.params),
            "hist": [self.hist_bins, self.hist_max_slowdown],
        }
        blob = json.dumps(payload, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


class StreamAggregates:
    """Online per-manager aggregates — the whole memory footprint of a
    10^6-mix stream is these few (K,)- and (K, bins)-shaped arrays.

    Folds are plain float64 numpy in chunk order, so aggregates are
    bit-reproducible across resume (and independent of pipeline overlap,
    which never reorders folds).
    """

    def __init__(self, n_managers: int, hist_bins: int,
                 hist_max_slowdown: float):
        self.hist_bins = int(hist_bins)
        self.hist_max = float(hist_max_slowdown)
        # Uniform bins over [0, hist_max) with bin (hist_bins - 1) as the
        # overflow bucket; width excludes the overflow bin.
        self.bin_width = self.hist_max / (self.hist_bins - 1)
        k = int(n_managers)
        self.mix_count = np.zeros(k, dtype=np.int64)
        self.log_ws_sum = np.zeros(k, dtype=np.float64)
        self.slowdown_hist = np.zeros((k, self.hist_bins), dtype=np.int64)
        self.max_slowdown = np.zeros(k, dtype=np.float64)
        self.min_fairness = np.full(k, np.inf, dtype=np.float64)

    def fold(self, ws: np.ndarray, slowdown: np.ndarray,
             fairness: np.ndarray) -> None:
        """Fold one chunk: ws (K, M), slowdown (K, M, n), fairness (K, M)."""
        ws = np.asarray(ws, dtype=np.float64)
        slowdown = np.asarray(slowdown, dtype=np.float64)
        fairness = np.asarray(fairness, dtype=np.float64)
        k, m = ws.shape
        self.mix_count += m
        self.log_ws_sum += np.log(ws).sum(axis=1)
        bins = np.clip(
            (slowdown / self.bin_width).astype(np.int64),
            0, self.hist_bins - 1)
        for ki in range(k):
            self.slowdown_hist[ki] += np.bincount(
                bins[ki].ravel(), minlength=self.hist_bins)
        self.max_slowdown = np.maximum(
            self.max_slowdown, slowdown.max(axis=(1, 2)))
        self.min_fairness = np.minimum(
            self.min_fairness, fairness.min(axis=1))

    # -------------------------------------------------------- queries #

    def geomean_ws(self) -> np.ndarray:
        with np.errstate(invalid="ignore"):
            return np.exp(self.log_ws_sum / np.maximum(self.mix_count, 1))

    def slowdown_percentile(self, q: float) -> np.ndarray:
        """Histogram-sketch percentile per manager (q in (0, 1))."""
        out = np.zeros(len(self.mix_count), dtype=np.float64)
        for ki, hist in enumerate(self.slowdown_hist):
            total = hist.sum()
            if total == 0:
                out[ki] = np.nan
                continue
            target = q * total
            cum = np.cumsum(hist)
            b = int(np.searchsorted(cum, target))
            prev = cum[b - 1] if b > 0 else 0
            frac = ((target - prev) / hist[b]) if hist[b] else 0.0
            out[ki] = (b + frac) * self.bin_width
        return out

    # ---------------------------------------------- checkpoint pytree #

    def to_tree(self) -> Dict[str, np.ndarray]:
        return {
            "mix_count": self.mix_count,
            "log_ws_sum": self.log_ws_sum,
            "slowdown_hist": self.slowdown_hist,
            "max_slowdown": self.max_slowdown,
            "min_fairness": self.min_fairness,
        }

    def load_tree(self, tree: Dict[str, np.ndarray]) -> None:
        for key, value in self.to_tree().items():
            arr = np.asarray(tree[key], dtype=value.dtype)
            if arr.shape != value.shape:
                raise CheckpointMismatchError(
                    f"aggregate {key!r} shape {arr.shape} != "
                    f"expected {value.shape}")
            setattr(self, {"mix_count": "mix_count",
                           "log_ws_sum": "log_ws_sum",
                           "slowdown_hist": "slowdown_hist",
                           "max_slowdown": "max_slowdown",
                           "min_fairness": "min_fairness"}[key], arr)


@dataclasses.dataclass
class StreamReport:
    """The deliverable of one stream run (resumed or not)."""

    manager_names: List[str]
    n_mixes: int
    mixes_covered: int
    coverage: float
    chunks: int
    quarantined: List[Tuple[int, str]]
    retries: int
    geomean_ws: Dict[str, float]
    p50_slowdown: Dict[str, float]
    p90_slowdown: Dict[str, float]
    p99_slowdown: Dict[str, float]
    max_slowdown: Dict[str, float]
    min_fairness: Dict[str, float]
    straggler_events: int
    straggler_mitigations: int
    wall_s: float
    resumed_from: Optional[int]
    aggregates: StreamAggregates

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d.pop("aggregates")
        d["quarantined"] = [[int(c), r] for c, r in self.quarantined]
        return d


@dataclasses.dataclass
class _ChunkOutcome:
    """What the worker thread hands back for one chunk."""

    status: str                       # "ok" | "dispatch_failed"
    retries: int = 0
    error: Optional[str] = None
    ws: Optional[np.ndarray] = None          # (K, M_valid)
    slowdown: Optional[np.ndarray] = None    # (K, M_valid, n)
    fairness: Optional[np.ndarray] = None    # (K, M_valid)
    finite: Optional[np.ndarray] = None      # (K, M_valid) bool


def _spec_plant(m: int, n: int, total_units: int, total_bandwidth: float):
    """The duck-typed plant ``sweep._manager_spec`` needs — shape + caps."""
    import types

    return types.SimpleNamespace(
        n_mixes=m, n_clients=n, total_cache_units=total_units,
        total_bandwidth=total_bandwidth)


def _build_specs(cfg: StreamConfig, n: int):
    """One TimelineSpec per manager at the chunk shape (built once; every
    chunk shares schedules and step-0 state, so jit stays warm)."""
    from repro.sim.sweep import _manager_spec

    plant = _spec_plant(cfg.chunk_size, n, cfg.total_cache_units,
                        cfg.total_bandwidth)
    return [_manager_spec(plant, name, cfg.total_ms, cfg.params)
            for name in cfg.manager_names]


def _chunk_metrics(ipc_stack, w_accs, base_ipc):
    """The in-trace metrics + finite-guard program (runs on device).

    ipc_stack (K, M, n) time-weighted IPC sums; w_accs (K, 1, 1);
    base_ipc (M, n).  Returns ws (K, M), slowdown (K, M, n), fairness
    (K, M) and the finite guard (K, M) — ``isfinite`` reduced over apps in
    the same program, so divergence detection costs no extra transfer and
    no host-side row scan.
    """
    import jax.numpy as jnp

    ipc = ipc_stack / w_accs
    speedup = ipc / base_ipc[None]
    ws = jnp.mean(speedup, axis=-1)
    slowdown = base_ipc[None] / ipc
    fairness = jnp.min(speedup, axis=-1) / jnp.max(speedup, axis=-1)
    finite = (jnp.isfinite(ipc).all(axis=-1)
              & jnp.isfinite(base_ipc).all(axis=-1)[None]
              & (ipc > 0.0).all(axis=-1))
    return ws, slowdown, fairness, finite


class _StreamRunner:
    """One stream execution: pipeline, fault handling, checkpointing."""

    def __init__(self, cfg: StreamConfig, plan: Optional[FaultPlan],
                 overlap: bool, sleep_fn: Callable[[float], None]):
        self.cfg = cfg
        self.plan = plan or FaultPlan()
        self.overlap = overlap
        self.sleep_fn = sleep_fn
        self.names = cfg.manager_names
        self.K = len(self.names)
        self.n = cfg.scenario.apps_per_mix
        self.specs = _build_specs(cfg, self.n)
        self.agg = StreamAggregates(self.K, cfg.hist_bins,
                                    cfg.hist_max_slowdown)
        self.quarantined: List[Tuple[int, str]] = []
        self.retries = 0
        self.cursor = 0
        self.resumed_from: Optional[int] = None
        self.watchdog = StragglerWatchdog(
            threshold=cfg.watchdog_threshold,
            warmup=cfg.watchdog_warmup)
        self._consecutive_quarantines = 0
        self._metrics_jit = None
        self.ckpt = None
        if cfg.checkpoint_dir:
            from repro.checkpoint import CheckpointManager

            self.ckpt = CheckpointManager(
                pathlib.Path(cfg.checkpoint_dir), keep=3)

    # ----------------------------------------------------- checkpoint #

    def try_resume(self) -> None:
        if self.ckpt is None:
            return
        restored = self.ckpt.restore_latest(self.agg.to_tree())
        if restored is None:
            return
        step, tree, extra = restored
        if extra.get("fingerprint") != self.cfg.fingerprint():
            raise CheckpointMismatchError(
                f"checkpoint at {self.cfg.checkpoint_dir} belongs to a "
                f"different stream (fingerprint "
                f"{extra.get('fingerprint')!r} != "
                f"{self.cfg.fingerprint()!r})")
        self.agg.load_tree(tree)
        self.cursor = int(extra["cursor"])
        self.resumed_from = self.cursor
        self.quarantined = [(int(c), str(r))
                            for c, r in extra.get("quarantined", [])]
        self.retries = int(extra.get("retries", 0))

    def checkpoint(self, next_chunk: int) -> None:
        if self.ckpt is None:
            return
        self.ckpt.save(
            next_chunk, self.agg.to_tree(),
            extra={
                "fingerprint": self.cfg.fingerprint(),
                "cursor": next_chunk,
                "quarantined": [[int(c), r] for c, r in self.quarantined],
                "retries": self.retries,
                "seed": self.cfg.seed,
            })

    # ------------------------------------------------------- pipeline #

    def _valid_rows(self, chunk_idx: int) -> int:
        start = chunk_idx * self.cfg.chunk_size
        return min(self.cfg.chunk_size, self.cfg.n_mixes - start)

    def _generate(self, chunk_idx: int) -> Dict[str, np.ndarray]:
        params = scenario_chunk(self.cfg.scenario, self.cfg.seed,
                                chunk_idx, self.cfg.chunk_size)
        params.pop("mix_indices", None)
        return params

    def _dispatch_and_fetch(self, chunk_idx: int,
                            params: Dict[str, np.ndarray]) -> _ChunkOutcome:
        """The worker-thread body: retrying dispatch, then the blocking
        fetch of the chunk's metrics.  Runs fully off the host thread in
        overlap mode so generation of the next chunk proceeds meanwhile.
        """
        cfg = self.cfg
        attempt = 0
        while True:
            try:
                self.plan.on_dispatch(chunk_idx, attempt)
                pending = timeline_jax.run_timelines_async(
                    params, self.specs,
                    total_units=cfg.total_cache_units,
                    total_bandwidth=cfg.total_bandwidth,
                    llc_extra_cycles=cfg.llc_extra_cycles,
                    min_ways=cfg.params.min_ways,
                    speedup_threshold=cfg.params.speedup_threshold,
                    min_bandwidth_allocation=(
                        cfg.params.min_bandwidth_allocation),
                    atd_decay=cfg.params.atd_decay,
                    bandwidth_delay_decay=cfg.params.bandwidth_delay_decay,
                    # Chunk c's grid buffers are donated to its program:
                    # the stream never holds two chunks' (K, M, n) grids
                    # live at once (results/dispatch count unchanged).
                    donate=True,
                )
                base = self._baseline(params)
                break
            except Exception as exc:  # noqa: BLE001 — quarantine barrier
                if attempt >= cfg.retry.max_retries:
                    return _ChunkOutcome(
                        status="dispatch_failed", retries=attempt,
                        error=f"{type(exc).__name__}: {exc}")
                self.sleep_fn(cfg.retry.delay(attempt))
                attempt += 1
                self.retries += 1

        import jax
        import jax.numpy as jnp

        with x64_context():
            ipc_stack = jnp.stack(
                [d["ipc_acc"] for d in pending.device_results])
            if self.plan.poisons(chunk_idx):
                # Poison the device-resident results so the injected
                # divergence flows through the SAME in-trace finite guard
                # a genuine solver blow-up would hit.
                ipc_stack = jnp.full_like(ipc_stack, np.nan)
            if self._metrics_jit is None:
                self._metrics_jit = jax.jit(_chunk_metrics)
            w_accs = np.asarray(pending.w_accs,
                                dtype=np.float64)[:, None, None]
            record_dispatch()
            ws, slowdown, fairness, finite = self._metrics_jit(
                ipc_stack, w_accs, base)
        valid = self._valid_rows(chunk_idx)
        return _ChunkOutcome(
            status="ok", retries=attempt,
            ws=np.asarray(ws)[:, :valid],
            slowdown=np.asarray(slowdown)[:, :valid],
            fairness=np.asarray(fairness)[:, :valid],
            finite=np.asarray(finite)[:, :valid])

    def _baseline(self, params: Dict[str, np.ndarray]):
        """Shared unpartitioned baseline for this chunk (device array)."""
        cfg = self.cfg
        m = cfg.chunk_size
        units, bw = equal_share(self.n, cfg.total_cache_units,
                                cfg.total_bandwidth)
        ss = memsys_jax.evaluate(
            params,
            np.tile(units.astype(np.float64), (m, 1)),
            np.tile(bw, (m, 1)),
            np.zeros((m, self.n), dtype=bool),
            cache_partitioned=False,
            bandwidth_partitioned=False,
            total_cache_units=float(cfg.total_cache_units),
            total_bandwidth_gbps=cfg.total_bandwidth,
            llc_extra_cycles=cfg.llc_extra_cycles,
        )
        return ss.ipc

    def _quarantine(self, chunk_idx: int, reason: str) -> None:
        self.quarantined.append((chunk_idx, reason))
        self._consecutive_quarantines += 1
        if (self._consecutive_quarantines
                > self.cfg.max_consecutive_quarantines):
            raise StreamAbortedError(
                f"{self._consecutive_quarantines} consecutive chunks "
                f"quarantined (last: chunk {chunk_idx}: {reason}); the "
                f"stream is broken, not degraded — aborting instead of "
                f"reporting near-zero coverage")

    def _finish(self, chunk_idx: int, outcome: _ChunkOutcome,
                wall_s: float) -> None:
        """Fold/quarantine one fetched chunk (host thread, in order)."""
        wall_s += self.plan.straggle_seconds(chunk_idx)
        # Mitigation on a single host is log-only; counts go in the report.
        self.watchdog.observe(chunk_idx, wall_s)
        if outcome.status != "ok":
            self._quarantine(
                chunk_idx, f"dispatch_failed after "
                f"{outcome.retries} retries ({outcome.error})")
            return
        if not outcome.finite.all():
            k, m = np.argwhere(~outcome.finite)[0]
            err = NumericalDivergenceError(
                self.names[int(k)],
                chunk_idx * self.cfg.chunk_size + int(m),
                chunk_idx)
            if self.cfg.on_divergence == "raise":
                raise err
            self._quarantine(chunk_idx, str(err))
            return
        self._consecutive_quarantines = 0
        self.agg.fold(outcome.ws, outcome.slowdown, outcome.fairness)

    def run(self) -> StreamReport:
        cfg = self.cfg
        t_start = time.monotonic()
        n_chunks = cfg.n_chunks
        pool = (concurrent.futures.ThreadPoolExecutor(max_workers=1)
                if self.overlap else None)
        # Depth-2 pipeline: chunk c is SUBMITTED to the worker before
        # chunk c-1 is joined, so the fold/checkpoint of c-1 and the
        # generation of c+1 run on the main thread while the worker is
        # inside chunk c's compute/fetch.  Joins are FIFO, so aggregate
        # folds happen in chunk order and bit-parity with the serial
        # path is preserved.
        queue: List[Tuple[int, object, float]] = []
        try:
            for c in range(self.cursor, n_chunks):
                self.plan.on_chunk_start(c)
                params = self._generate(c)
                if self.overlap:
                    t0 = time.monotonic()
                    fut = pool.submit(self._dispatch_and_fetch, c, params)
                    queue.append((c, fut, t0))
                    if len(queue) > 1:
                        self._join(queue.pop(0))
                else:
                    t0 = time.monotonic()
                    outcome = self._dispatch_and_fetch(c, params)
                    self._finish(c, outcome, time.monotonic() - t0)
                    self._maybe_checkpoint(c)
            while queue:
                self._join(queue.pop(0))
        finally:
            if pool is not None:
                pool.shutdown(wait=False, cancel_futures=True)
        return self._report(time.monotonic() - t_start)

    def _join(self, pending: Tuple[int, object, float]) -> None:
        c, fut, t0 = pending
        outcome = fut.result()
        self._finish(c, outcome, time.monotonic() - t0)
        self._maybe_checkpoint(c)

    def _maybe_checkpoint(self, chunk_idx: int) -> None:
        done = chunk_idx + 1
        if self.ckpt is not None and (done % self.cfg.checkpoint_every == 0
                                      or done == self.cfg.n_chunks):
            self.checkpoint(done)

    def _report(self, wall_s: float) -> StreamReport:
        cfg = self.cfg
        quarantined_mixes = sum(self._valid_rows(c)
                                for c, _ in self.quarantined)
        covered = cfg.n_mixes - quarantined_mixes
        per = {}
        for label, arr in (
                ("geomean_ws", self.agg.geomean_ws()),
                ("p50", self.agg.slowdown_percentile(0.50)),
                ("p90", self.agg.slowdown_percentile(0.90)),
                ("p99", self.agg.slowdown_percentile(0.99)),
                ("max_slowdown", self.agg.max_slowdown),
                ("min_fairness", self.agg.min_fairness)):
            per[label] = {name: round(float(v), 6)
                          for name, v in zip(self.names, arr)}
        return StreamReport(
            manager_names=list(self.names),
            n_mixes=cfg.n_mixes,
            mixes_covered=covered,
            coverage=covered / cfg.n_mixes,
            chunks=cfg.n_chunks,
            quarantined=list(self.quarantined),
            retries=self.retries,
            geomean_ws=per["geomean_ws"],
            p50_slowdown=per["p50"],
            p90_slowdown=per["p90"],
            p99_slowdown=per["p99"],
            max_slowdown=per["max_slowdown"],
            min_fairness=per["min_fairness"],
            straggler_events=len(self.watchdog.events),
            straggler_mitigations=self.watchdog.mitigations,
            wall_s=wall_s,
            resumed_from=self.resumed_from,
            aggregates=self.agg,
        )


def run_stream(
    cfg: StreamConfig,
    *,
    fault_plan: Optional[FaultPlan] = None,
    resume: bool = False,
    overlap: bool = True,
    sleep_fn: Callable[[float], None] = time.sleep,
) -> StreamReport:
    """Run (or resume) a streaming sweep.

    Args:
      cfg: stream shape + robustness policy.
      fault_plan: injected faults (tests/smokes); ``None`` = healthy run.
      resume: restore aggregates/cursor/quarantine from
        ``cfg.checkpoint_dir``'s latest complete checkpoint; a fresh run
        otherwise (an existing mismatched checkpoint raises
        :class:`CheckpointMismatchError` rather than being overwritten
        with data from a different stream).
      overlap: double-buffer (device computes chunk c while the host
        generates chunk c+1); ``False`` = serial chunk dispatch, the
        bench's comparison baseline.
      sleep_fn: injected for backoff in tests (defaults to real sleep).

    Returns a :class:`StreamReport`; ``report.aggregates`` carries the raw
    sketches for bit-exact comparison.
    """
    runner = _StreamRunner(cfg, fault_plan, overlap, sleep_fn)
    if resume:
        runner.try_resume()
    return runner.run()


__all__ = [
    "CheckpointMismatchError", "NumericalDivergenceError", "RetryPolicy",
    "StreamAbortedError", "StreamAggregates", "StreamConfig",
    "StreamReport", "run_stream",
]
