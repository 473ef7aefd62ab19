"""CPU tests of the chip benchmark's Zamba2 serving cell at tiny widths:
the program's forward and token-by-token decode agree with the plain
float32 reference; the harness reads ``correct`` on the timed path, and
reads not correct where the recurrent state is not reset, where block A
serves every site, where the adapters are dropped, and for the float8 and
the bfloat16-state controls; the counts and the HBM reader hold."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

import chip_bench_util as u
from bench import harness, hybrid_counts, peaks
from reference.zamba2 import Dims, forward, make_weights

#: Zamba2 at tiny widths, under the published names: 12 layers with sites
#: at 2, 5, 8, 11 (blocks A, B, A, B), attention of 4 heads of 32 over
#: 2 x 64, 8 SSM heads of 16 in 2 groups, state 16, adapters of rank 8.
TINY_ZAMBA2 = {
    "source": "test", "hidden_size": 64, "num_hidden_layers": 12,
    "hybrid_layer_ids": [2, 5, 8, 11],
    "layers_block_type": ["hybrid" if i in (2, 5, 8, 11) else "mamba"
                          for i in range(12)],
    "num_mem_blocks": 2, "adapter_rank": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "attention_head_dim": 32,
    "ffn_hidden_size": 128, "vocab_size": 512, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "n_mamba_heads": 8, "mamba_headdim": 16,
    "mamba_d_state": 16, "mamba_ngroups": 2, "mamba_d_conv": 4,
    "mamba_expand": 2, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 1e-4}
TINY = Dims.from_config(TINY_ZAMBA2)


def _tiny_hybrid():
    """The Zamba2 cell's own limits, at 8 slots and 96 steps a call."""
    wl = json.loads((u.BENCH / "workloads" / "serve-chat-tenants-zamba2.json")
                    .read_text())
    wl.update(max_steps=96, trace_seconds=1)
    wl["engine"].update(batch_slots=8, max_len=96, total_pages=48,
                        reconfig_every_steps=8)
    return wl


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``u.bench_copy`` with a ``tiny-hybrid`` cell added as new files,
    on the tiny chat traffic of ``tiny-serve``."""
    root = u.bench_copy(tmp_path_factory.mktemp("hybrid"))
    b = root / "benchmarks" / "chip"
    (b / "configs" / "tiny-zamba2.json").write_text(json.dumps(TINY_ZAMBA2))
    (b / "workloads" / "tiny-hybrid.json").write_text(
        json.dumps(_tiny_hybrid()))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(
        {"name": "tiny-zamba2", "source": "test", "reduced": [],
         "why": "test", "file": "benchmarks/chip/configs/tiny-zamba2.json"})
    man["workloads"].append(
        {"name": "tiny-hybrid", "config": "tiny-zamba2",
         "traffic": "tiny-chat", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "serve-chat-tenants-zamba2" in m.get("workloads", ()):
            m["workloads"].append("tiny-hybrid")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, seed):
    return u.run_tiny(root, "tiny-hybrid", seed=seed, seconds=0.05)


def test_the_hybrid_cell_reads_correct_on_its_timed_path(root):
    res = _run(root, 2 ** 31 + 9)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["window"]["compiles_in_window"] == 0
    checks = {c["name"]: c["value"] for c in res["checks"]}
    assert checks["schedule_mismatches"] == 0
    # the state of every tenant's sampled live request was judged, and is
    # kept in float32
    assert 0 < checks["state_err"] < float("inf")
    assert checks["state_bf16_share"] < 1e-3


# ----------------------------------------------- faults under the timed path


def _stale_state(monkeypatch):
    """The reset taken out: a slot's next request starts from the conv
    window and SSM state its last request left."""
    from repro.models import ssm

    monkeypatch.setattr(ssm, "start_fresh", lambda fresh, c, s: (c, s))


def _block_a_everywhere(monkeypatch):
    """One shared block at every site, where the sites take the two in
    turn."""
    from repro.models import hybrid

    monkeypatch.setattr(hybrid, "_block", lambda params, s:
                        params["blocks"][0])


def _no_adapters(monkeypatch):
    """The sites' MLP adapters dropped."""
    from repro.models import hybrid

    monkeypatch.setattr(hybrid, "_adapter", lambda site, m: 0.0)


@pytest.mark.parametrize("fault", [_stale_state, _block_a_everywhere,
                                   _no_adapters], ids=lambda f: f.__name__)
def test_a_broken_hybrid_reads_not_correct(root, monkeypatch, fault):
    """The whole run, past the chip check, with the program the engine
    compiles broken underneath."""
    fault(monkeypatch)
    res = _run(root, 11)
    assert res["correct"] is False, res["checks"]
    checks = {c["name"]: c for c in res["checks"]}
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
    assert checks["state_err"]["value"] > checks["state_err"]["limit"]
    assert checks["schedule_mismatches"]["value"] == 0


def test_the_float8_control_reads_not_correct(root):
    """The reference with float8 matrices and K/V in the program's place,
    judged against the cell's limits, as ``calibrate.py`` does on the
    chip; and the reference with its SSM state in bfloat16.  That state
    moves the served ids and the state itself less than the program's
    own bfloat16 matrices do, so the logit gap and the state error pass
    it: the share of its entries that bfloat16 holds exactly does not."""
    import jax

    ctx, _man, entry = harness.prepare(
        root, "tiny-hybrid", 13, False, root / "benchmarks" / "chip",
        jax.devices()[:1])
    state = entry.setup(ctx)
    record = entry.window(state, 0.05, ctx)
    entry.release(state)
    prog = entry.check(state, record, ctx)[0]
    assert harness.verdict(prog), prog
    ctl = entry.check(state, entry.control(state, ctx), ctx)[0]
    assert not harness.verdict(ctl)
    gap = {c["name"]: c["value"] for c in prog}["logit_gap"]
    ctl_gap = next(c for c in ctl if c["name"] == "logit_gap")
    assert ctl_gap["value"] > ctl_gap["limit"] and ctl_gap["value"] > 5 * gap
    ctl_err = next(c for c in ctl if c["name"] == "state_err")
    assert ctl_err["value"] > ctl_err["limit"]
    state_bf16 = entry.check(
        state, entry.control_in(state, ctx, "bf16_state"), ctx)[0]
    assert not harness.verdict(state_bf16), state_bf16
    failing = [c["name"] for c in state_bf16 if c["value"] > c["limit"]]
    assert failing == ["state_bf16_share"], state_bf16


def test_the_bf16_share_counts_what_bfloat16_holds_exactly():
    import ml_dtypes

    entry = harness.load_module(u.BENCH / "entries" / "serve_hybrid.py",
                                "bench_entry_serve_hybrid_share")
    x = np.random.default_rng(3).normal(size=(4, 1000)).astype(np.float32)
    assert entry._bf16_share(x) < 1e-3
    rounded = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert entry._bf16_share(rounded) == 1.0
    # zeros say nothing of the precision: left out
    x[:3] = 0
    assert entry._bf16_share(np.concatenate([x, rounded[:1]])) == (
        pytest.approx(0.5 * (entry._bf16_share(x[3:]) + 1.0)))
    assert entry._bf16_share(np.zeros(5, np.float32)) == 1.0


# ------------------------------------------------------------- references


@pytest.fixture(scope="module")
def program():
    """The program's hybrid in float32 on the reference's weights."""
    import jax
    import jax.numpy as jnp

    entry = harness.load_module(u.BENCH / "entries" / "serve_hybrid.py",
                                "bench_entry_serve_hybrid_ref")
    cfg = dataclasses.replace(entry._model_config(TINY),
                              param_dtype="float32", ssm_chunk=8)
    w = make_weights(TINY, 21, cfg.padded_vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          entry._program_layout(w, TINY))
    tokens = np.random.default_rng(21).integers(0, TINY.vocab_size, (2, 40))
    return cfg, w, params, tokens


# The program runs the recurrence by chunks (``ssd_chunked``) or one
# position at a time through its cache, and the reference one position at
# a time without a cache, all in float32 at the highest matmul precision:
# they differ by float32 rounding only, about 3e-5 on logits of up to
# about 4.  1e-4 is far below what a departure in the mathematics moves
# (the float8 control moves the logits by about 2, bfloat16 state by 2e-2).
TOL = 1e-4


def test_the_programs_forward_matches_the_reference(program):
    import jax
    import jax.numpy as jnp

    from repro.models import hybrid, transformer

    cfg, w, params, tokens = program
    with jax.default_matmul_precision("highest"):
        x = transformer.embed(params, cfg, jnp.asarray(tokens))
        prog = transformer.logits_fn(
            params, cfg, hybrid.forward(params, cfg, x, jnp.arange(40)))
    np.testing.assert_allclose(np.asarray(forward(w, TINY, tokens)),
                               np.asarray(prog), atol=TOL, rtol=0)


def test_the_programs_decode_token_by_token_matches_the_reference(program):
    """Each row at its own position, the second starting 7 steps later
    from a slot whose state holds another sequence: the reset at its
    position 0 gives it the reference's logits all the same."""
    import jax
    import jax.numpy as jnp

    from repro.models import hybrid

    cfg, w, params, tokens = program
    ref = np.asarray(forward(w, TINY, tokens))
    cache = hybrid.init_cache(cfg, 2, 48, jnp.float32)
    other = np.random.default_rng(5).integers(0, TINY.vocab_size, 7)
    got = [[], []]
    step = jax.jit(lambda c, t, p: hybrid.decode_step(params, cfg, c, t, p))
    with jax.default_matmul_precision("highest"):
        for k in range(47):
            pos = np.array([k, k - 7 if k >= 7 else k])
            tok = np.array([tokens[0, k] if k < 40 else 0,
                            tokens[1, k - 7] if k >= 7 else other[k]])
            logits, cache = step(cache, jnp.asarray(tok[:, None], jnp.int32),
                                 jnp.asarray(pos, jnp.int32))
            if k < 40:
                got[0].append(np.asarray(logits[0, 0]))
            if k >= 7:
                got[1].append(np.asarray(logits[1, 0]))
    for row in (0, 1):
        np.testing.assert_allclose(ref[row], np.stack(got[row]), atol=TOL,
                                   rtol=0)


# ----------------------------------------------------------------- counts


def _d24():
    return Dims.from_config(json.loads(
        (u.BENCH / "configs" / "zamba2-7b-d24.json").read_text()))


def test_counts_match_hand_counts():
    """At the cell's configuration: weights 2.733e9 parameters in
    bfloat16, 112.2 MB of state and K/V a slot at 576 positions."""
    z = _d24()
    assert hybrid_counts.weight_bytes(z) == 2 * 2_733_050_240
    assert hybrid_counts.state_bytes(z) == 24 * 4 * (112 * 64 * 64
                                                     + 3 * 7424)
    assert hybrid_counts.kv_bytes_per_position(z) == 4 * 28_672
    mamba = 3584 * 14704 + 7168 * 3584
    site = (7168 * 3 * 7168 + 7168 * 3584 + 3 * 3584 * 14336
            + 3584 * 128 + 128 * 28672 + 3584 * 3584)
    assert hybrid_counts.matrix_params(z) == (24 * mamba + 4 * site
                                              + 3584 * 32000)
    # one slot-step at position 0 and one at position 9, over 3 steps
    w = hybrid_counts.work(z, 3, np.bincount([0, 9], minlength=16))
    per_pos = 4 * 4 * 32 * 224
    ssm = 24 * (2 * 4 * 7424 + 6 * 112 * 64 * 64)
    assert w["flops"] == 2 * (2 * hybrid_counts.matrix_params(z) + ssm) \
        + per_pos * (1 + 10)
    assert w["bytes"] == (3 * 2 * 2_733_050_240
                          + 2 * 2 * hybrid_counts.state_bytes(z)
                          + 4 * 28_672 * (1 + 10))


def test_the_hbm_share_reads_the_least_bytes_over_the_step_time():
    reader = harness.load_module(
        u.BENCH / "metrics" / "serve.step_hbm_share.py",
        "bench_metric_test_serve_step_hbm_share")
    trace = type("Trace", (), {"module_s": {"jit__interval": 2.0},
                               "module_calls": {"jit__interval": 4}})()
    rec = {"kind": "serve", "calls": [{"steps": 16, "intervals": 4}],
           "work": {"steps": 16, "flops": 1.0, "bytes": 16 * 4.095e8}}
    ctx = type("Ctx", (), {"peaks": peaks.lookup("TPU v5 lite")})()
    # 0.5 s a module call of 4 steps: 0.125 s a step, 4.095e8 bytes
    assert reader.read(rec, trace, ctx) == pytest.approx(
        100 * 4.095e8 / 0.125 / 819e9)
    # the dense entry leaves no byte count: nothing to read
    assert reader.read(dict(rec, work={"steps": 16, "flops": 1.0}), trace,
                       ctx) is None
