"""The Zamba2 hybrid on the serving path: both engines serve a request
from a reused slot as they serve it alone (recurrent state starts from zero
at position 0), the device engine keeps what its last step left in each
slot, and the decode names its Mamba and shared-block work for the trace;
the parameter counts follow the published block.  The comparison with the plain reference is in
``tests/chip_bench/test_chip_bench_hybrid.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import hybrid
from repro.models.model import Model
from repro.serving import (
    EngineConfig,
    JitServingEngine,
    Request,
    ServingEngine,
)

#: Zamba2 at tiny widths: 12 layers, sites at 2, 5, 8, 11 (blocks A, B,
#: A, B), 8 SSM heads of 16 in 2 groups, state 16, adapters of rank 8.
TINY = dataclasses.replace(
    configs.get_smoke("zamba2-7b"), n_layers=12,
    hybrid_layer_ids=(2, 5, 8, 11))


@pytest.fixture(scope="module")
def model():
    m = Model(TINY)
    return m, m.init(jax.random.PRNGKey(0))


def _requests(n=10, seed=5):
    """Requests of one shape (prompt 5, 6 new tokens), so that a request
    served alone compiles once for all."""
    rng = np.random.default_rng(seed)
    return [Request(stream=i % 2,
                    prompt=rng.integers(1, TINY.vocab_size, 5
                                        ).astype(np.int32),
                    max_new_tokens=6)
            for i in range(n)]


def _copy(reqs):
    return [Request(stream=r.stream, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in reqs]


ENGINE = EngineConfig(batch_slots=3, max_len=24, total_pages=12,
                      page_tokens=4, reconfig_every_steps=4)


def _engines(model):
    m, params = model
    return {"jit": lambda: JitServingEngine(m, params, 2, ENGINE),
            "host": lambda: ServingEngine(m, params, 2, ENGINE)}


@pytest.mark.parametrize("kind", ["jit", "host"])
def test_a_request_from_a_reused_slot_gets_the_tokens_it_gets_alone(
        model, kind):
    """Ten requests through three slots: most start in a slot that served
    another request, whose conv window and SSM state it must not see."""
    make = _engines(model)[kind]
    reqs = _requests()
    make().run(reqs)
    single = make()
    for r in reqs:
        alone = _copy([r])
        single.run(alone)
        assert r.generated == alone[0].generated, r


def test_both_engines_serve_the_same_tokens(model):
    out = []
    for kind, make in _engines(model).items():
        reqs = _requests(seed=9)
        make().run(reqs)
        out.append([r.generated for r in reqs])
    assert out[0] == out[1]


def test_a_stale_state_is_what_the_reset_removes(model, monkeypatch):
    """Without the reset, a request in a reused slot is served other
    tokens: the test above would see the fault."""
    from repro.models import ssm

    monkeypatch.setattr(ssm, "start_fresh", lambda fresh, c, s: (c, s))
    m, params = model
    reqs = _requests()
    JitServingEngine(m, params, 2, ENGINE).run(reqs)
    alone, single = [], JitServingEngine(m, params, 2, ENGINE)
    for r in reqs:
        one = _copy([r])
        single.run(one)
        alone.append(one[0].generated)
    assert any(r.generated != a for r, a in zip(reqs, alone))


def test_the_decode_names_its_mamba_and_shared_block_work(model):
    """The scopes that ``bench/scopes.py`` splits a trace by are in the
    lowered decode, inside the engine's interval program too."""
    m, params = model
    cache = m.init_cache(3, 16)
    text = jax.jit(m.decode_step).lower(
        params, cache, jnp.zeros((3, 1), jnp.int32),
        jnp.zeros((3,), jnp.int32)).as_text(debug_info=True)
    assert "cbp.serve.mamba" in text and "cbp.serve.shared_block" in text
    eng = JitServingEngine(m, params, 2, ENGINE)
    state = eng._build_state(_requests())
    text = eng._interval_jit.lower(state, params, jnp.int32(8)).as_text(
        debug_info=True)
    assert "cbp.serve.shared_block" in text


def test_the_engine_keeps_each_slots_request_position_and_state(model):
    """After a run cut short, each live slot names its request and the
    tokens it has been fed, and holds the SSM state of those tokens decoded
    alone from position 0.  A batch of three and a batch of one round
    differently (up to 5e-4 on states of about 1); another slot's state
    differs by about 1."""
    m, params = model
    reqs = _requests()
    eng = JitServingEngine(m, params, 2, ENGINE)
    eng.run(reqs, max_steps=17)
    live = [(s, i, p) for s, (i, p) in enumerate(zip(eng.slot_request,
                                                     eng.slot_pos))
            if i >= 0]
    assert live and all(p > 0 for _, _, p in live)
    # the last request admitted started in a slot another request left
    assert max(i for _, i, _ in live) >= ENGINE.batch_slots
    step = jax.jit(m.decode_step)
    for slot, i, pos in live:
        r = reqs[i]
        fed = np.concatenate([r.prompt, r.generated])[:pos]
        assert len(fed) == pos == len(r.prompt) + len(r.generated) - 1
        cache = m.init_cache(1, ENGINE.max_len)
        for k, tok in enumerate(fed):
            _, cache = step(params, cache, jnp.full((1, 1), tok, jnp.int32),
                            jnp.full((1,), k, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(eng.cache["state"][:, slot]),
            np.asarray(cache["state"][:, 0]), rtol=0, atol=2e-3)


@pytest.mark.parametrize("layers,sites,expected", [
    (81, (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77), 7_356_749_648),
    (24, (6, 11, 17, 23), 2_733_050_240),
], ids=["published-81", "d24"])
def test_param_count_follows_the_published_block(layers, sites, expected):
    """Per Mamba layer 78,437,456 (in_proj 3,584 x 14,704, conv and bias
    over 7,424 channels, dt_bias, A_log, D, gated norm, out_proj
    7,168 x 3,584, input norm); per shared block 333,982,208; per site
    16,973,824 (linear 3,584^2, adapter 3,584 x 128 + 128 x 28,672); the
    tied embedding 114,688,000 and the final norm."""
    cfg = dataclasses.replace(configs.get("zamba2-7b"), n_layers=layers,
                              hybrid_layer_ids=sites)
    mamba, block, site = 78_437_456, 333_982_208, 16_973_824
    assert cfg.param_count() == expected == (
        layers * mamba + 2 * block + len(sites) * site + 32000 * 3584
        + 3584)


def test_init_cache_holds_state_per_layer_and_kv_per_site():
    cache = hybrid.init_cache(TINY, 3, 16)
    assert cache["state"].shape == (12, 3, 2, 16, 64)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (12, 3, 3, 128 + 2 * 2 * 16)
    assert len(cache["k"]) == 4 and cache["k"][0].shape == (1, 3, 16, 4, 32)
    assert cache["k"][0].dtype == jnp.bfloat16


def test_cache_specs_shard_each_sites_kv_by_sequence():
    """The per-site K/V lists take the dense cache's rule (sequence over
    "model"), which ``attention_decode`` constrains them to as well."""
    from jax.sharding import Mesh

    from repro.launch import shardings

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    cache = jax.eval_shape(lambda: hybrid.init_cache(TINY, 3, 16))
    specs = shardings.cache_specs(TINY, cache, mesh)
    for name in ("k", "v"):
        assert all(s[2] == "model" for s in specs[name])
    assert specs["state"][4] == "model" and specs["conv"][3] == "model"
