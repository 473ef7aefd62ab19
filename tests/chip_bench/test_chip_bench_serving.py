"""CPU tests of the chip benchmark's serving cell at the qwen3 smoke
widths: the harness reads ``correct`` on the timed path and false when the
path is broken underneath, the schedule reference and the float32
reference agree with the program, and the counts, the traffic and the
readers hold."""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np
import pytest

import chip_bench_util as u
from bench import harness, model_counts, peaks, trace, traffic
from reference import serving as ref_serving
from reference.dense_lm import Dims, forward, make_weights

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY = Dims.from_config(u.TINY_LM)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return u.bench_copy(tmp_path_factory.mktemp("serve"))


def _run(root, seed):
    return u.run_tiny(root, "tiny-serve", seed=seed, seconds=0.05)


def test_the_serving_cell_reads_correct_on_its_timed_path(root):
    res = _run(root, 2 ** 31 + 5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["window"]["compiles_in_window"] == 0
    checks = {c["name"]: c["value"] for c in res["checks"]}
    assert checks["schedule_mismatches"] == 0
    assert checks["drained_calls"] == 0
    assert 0 <= checks["logit_gap"] < 0.05


# ----------------------------------------------- faults under the timed path


def _engine():
    from repro.serving import engine_jax

    return engine_jax.JitServingEngine


def _patch_finalize(monkeypatch, change):
    eng = _engine()
    orig = eng._finalize

    def _finalize(self, state, requests):
        orig(self, state, requests)
        change(self, requests)

    monkeypatch.setattr(eng, "_finalize", _finalize)


def _patch_decode(monkeypatch, change):
    from repro.models.model import Model

    orig = Model.decode_step

    def decode_step(self, params, cache, tokens, cur_len):
        logits, new = orig(self, params, cache, tokens, cur_len)
        return change(logits, cache, new)

    monkeypatch.setattr(Model, "decode_step", decode_step)


@pytest.fixture(scope="module")
def cell(root):
    """The tiny serving cell set up once: its context, entry and state,
    whose compiled engine the tests below share."""
    import jax

    ctx, _man, entry = harness.prepare(
        root, "tiny-serve", 13, False, root / "benchmarks" / "chip",
        jax.devices()[:1])
    return ctx, entry, entry.setup(ctx)


def _window_and_check(cell):
    ctx, entry, state = cell
    record = entry.window(state, 0.05, ctx)
    return record, entry.check(state, record, ctx)[0]


def _wrong_slot_share(monkeypatch):
    """One stream's slot share, planted wrong in what the engine reports."""
    def change(eng, requests):
        eng.slot_share = eng.slot_share + np.array([0.0, 1.0, 0.0, 0.0])
    _patch_finalize(monkeypatch, change)


def _tenth_ranked_token(monkeypatch):
    """A token altered where it is produced: the first served token of the
    longest finished request of each tenant becomes the one that the
    reference ranks 10th there."""
    weights = make_weights(TINY, 13, TINY.vocab_size)

    def change(eng, requests):
        for s in range(4):
            done = [r for r in requests if r.stream == s and r.generated
                    and len(r.generated) == r.max_new_tokens]
            if not done:
                continue
            r = max(done, key=lambda r: len(r.prompt) + len(r.generated))
            toks = np.zeros((1, 96), np.int32)        # one compiled shape
            toks[0, : len(r.prompt)] = r.prompt
            logits = np.asarray(forward(weights, TINY, toks))
            r.generated[0] = int(np.argsort(-logits[0, len(r.prompt) - 1])
                                 [9])
    _patch_finalize(monkeypatch, change)


@pytest.mark.parametrize("fault", [_wrong_slot_share, _tenth_ranked_token],
                         ids=lambda f: f.__name__)
def test_a_wrong_answer_from_the_engine_reads_not_correct(
        cell, monkeypatch, fault):
    fault(monkeypatch)
    _, checks = _window_and_check(cell)
    assert not harness.verdict(checks), checks


def _cache_left_unchanged(monkeypatch):
    """A step that returns its state unchanged: the decode step writes
    nothing to the KV cache."""
    _patch_decode(monkeypatch, lambda logits, old, new: (logits, old))


def _half_the_slots(monkeypatch):
    """Half of the batch left out: the second half of the slots takes the
    first half's logits."""
    def change(logits, old, new):
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), new
    _patch_decode(monkeypatch, change)


@pytest.mark.parametrize("fault", [_cache_left_unchanged, _half_the_slots],
                         ids=lambda f: f.__name__)
def test_a_broken_decode_step_reads_not_correct(root, monkeypatch, fault):
    """The whole run, past the chip check, with the decode step that the
    engine compiles broken underneath."""
    fault(monkeypatch)
    res = _run(root, 11)
    assert res["correct"] is False, res["checks"]


def test_the_float8_control_reads_not_correct(cell):
    """The reference with float8 matrices and K/V in the program's place,
    judged against the cell's limits, as ``calibrate.py`` does on the
    chip: it fails the logit gap.  (Its bfloat16 slot shares fail nothing
    here: equal tenants get equal shares, which bfloat16 holds exactly.)"""
    ctx, entry, state = cell
    _, checks = _window_and_check(cell)
    assert harness.verdict(checks), checks
    ctl = entry.check(state, entry.control(state, ctx), ctx)[0]
    assert not harness.verdict(ctl)
    prog = {c["name"]: c["value"] for c in checks}
    gap = next(c for c in ctl if c["name"] == "logit_gap")
    assert gap["value"] > gap["limit"] and gap["value"] > 5 * prog["logit_gap"]


# ------------------------------------------------------------- references


def test_the_schedule_does_not_depend_on_the_token_ids(cell):
    """Two draws of token ids over the same lengths give the engine one
    schedule, and it is the reference's, which reads only the lengths."""
    from repro.serving import Request

    _, _, state = cell
    eng = state.engine
    seen = []
    for ids_seed in (1, 2):
        ids = traffic.rng(ids_seed, 9)
        reqs = [Request(stream=r.stream, max_new_tokens=r.max_new_tokens,
                        prompt=ids.integers(0, TINY.vocab_size,
                                            len(r.prompt), dtype=np.int32))
                for r in state.queue]
        eng.run(reqs, max_steps=state.max_steps)
        seen.append((eng.steps, eng.reconfigs, eng.partition.tolist(),
                     eng.tokens_done.tolist(), eng.queue_wait.tolist(),
                     eng.slot_share.tolist(),
                     [-1 if r.generated is None else len(r.generated)
                      for r in reqs]))
    assert seen[0] == seen[1]
    ref = ref_serving.run([(r.stream, len(r.prompt), r.max_new_tokens)
                           for r in state.queue], state.engine_cfg,
                          state.max_steps)
    assert seen[0][:5] == (ref.steps, ref.reconfigs, ref.partition.tolist(),
                           ref.tokens_done.tolist(), ref.queue_wait.tolist())
    assert seen[0][6] == ref.generated.tolist()


def test_the_reference_forward_matches_the_programs_float32_forward():
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    entry = harness.load_module(u.BENCH / "entries" / "serve.py",
                                "bench_entry_serve_forward")
    cfg = dataclasses.replace(entry._model_config(TINY),
                              param_dtype="float32")
    w = make_weights(TINY, 21, cfg.padded_vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          entry._program_layout(w))
    tokens = np.asarray(traffic.rng(21, 0).integers(0, TINY.vocab_size,
                                                    (2, 40)))
    with jax.default_matmul_precision("highest"):
        x = transformer.embed(params, cfg, jnp.asarray(tokens))
        hidden = transformer.forward(params, cfg, x, jnp.arange(40))
        prog = transformer.logits_fn(params, cfg, hidden)
    ref = forward(w, TINY, tokens)
    np.testing.assert_allclose(np.asarray(ref),
                               np.asarray(prog)[..., :TINY.vocab_size],
                               atol=1e-5, rtol=0)


# ----------------------------------------------------------------- counts


def _dims(name):
    return Dims.from_config(json.loads(
        (u.BENCH / "configs" / f"{name}.json").read_text()))


def test_counts_match_hand_counts():
    m = _dims("mistral-7b-d8")
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, MLP 3 x
    # 4096x14336; the head 4096 x 32768
    mat = 8 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) \
        + 4096 * 32768
    assert model_counts.matrix_params(m) == mat
    # one slot-step at position 0 and one at position 9, over 3 steps
    w = model_counts.work(m, 3, np.bincount([0, 9], minlength=16))
    attn = 4 * 32 * 128 * 8
    assert w == {"steps": 3, "flops": 2 * (2 * mat) + attn * (1 + 10)}


# ---------------------------------------------------------------- traffic


def test_every_seed_serves_each_tenant_the_same_queue_with_its_own_ids():
    """The work of a call does not depend on the seed: every tenant gets
    the same two-turn conversations at the published mean lengths; only
    the ids differ."""
    tr = json.loads((u.BENCH / "traffic" / "lmsys-chat-tenants.json")
                    .read_text())
    a, b = traffic.requests(tr, 5, 32768), traffic.requests(tr, 2 ** 40, 32768)
    shape = [(r.stream, len(r.prompt), r.max_new_tokens) for r in a]
    assert shape == [(r.stream, len(r.prompt), r.max_new_tokens) for r in b]
    assert len(a) == 1024 and not np.array_equal(a[0].prompt, b[0].prompt)
    for s in range(4):
        mine = [(p, n) for st, p, n in shape if st == s]
        assert mine == [(69, 214), (353, 215)] * 128
    for r in a:
        assert r.prompt.min() >= 0 and r.prompt.max() < 32768
    # new prompts and responses keep the published means exactly
    assert [traffic.whole(69.5, k) for k in range(4)] == [69, 70, 69, 70]
    assert sum(traffic.whole(214.5, k) for k in range(256)) == 214.5 * 256


# ----------------------------------------------------- trace and readers


def _metric(name):
    return harness.load_module(u.BENCH / "metrics" / f"{name}.py",
                               "bench_metric_test_" + name.replace(".", "_"))


SERVING_READERS = ["serve.step_mfu", "serve.idle_share",
                   "serve.slot_occupancy"]


def test_serving_readers_on_a_recorded_trace(tmp_path):
    """A trace recorded on a TPU v5e of the tiny serving cell's window
    (one call of 16 steps, 8 slots, two intervals), with the record that
    the window and the check left."""
    import gzip
    import shutil

    xplane = tmp_path / "serve_trace.xplane.pb"
    with gzip.open(DATA / "serve_trace.xplane.pb.gz") as f, \
            open(xplane, "wb") as out:
        shutil.copyfileobj(f, out)
    summary = trace.summarize(trace.load(str(xplane)))
    assert summary.module_calls["jit__interval"] == 2 and not summary.cut
    rec = json.loads((DATA / "serve_trace.record.json").read_text())
    for c in rec["calls"]:
        c["tokens_done"] = np.asarray(c["tokens_done"])
    ctx = type("Ctx", (), {"window_s": rec["window_s"],
                           "peaks": peaks.lookup("TPU v5 lite")})()
    got = {m: _metric(m).read(rec, summary, ctx) for m in SERVING_READERS}
    assert all(v is not None for v in got.values()), got
    step_s = summary.module_s["jit__interval"] / 16
    w = rec["work"]
    assert got["serve.step_mfu"] == pytest.approx(
        100 * w["flops"] / 16 / 197e12 / step_s)
    assert 0 < got["serve.step_mfu"] <= 100
    assert got["serve.idle_share"] == pytest.approx(
        100 * (1 - summary.busy_s / rec["window_s"]))
    assert got["serve.slot_occupancy"] == 100.0
    # the sweep cell's readers find nothing here, and these nothing there
    assert _metric("sim.scan_device_ms").read(rec, summary, ctx) is None
    assert _metric("serve.step_mfu").read(
        {"kind": "sweep"}, summary, ctx) is None
    # without the interval module, the step readers read nothing
    other = dataclasses.replace(summary, module_s={}, module_calls={})
    assert _metric("serve.step_mfu").read(rec, other, ctx) is None
