"""Cohort decode picks every row's next token in one device call and reads
them back in one host read per step: the served tokens equal a row-by-row
argmax over the same cohort logits, sampled rows stay reproducible, and a
cohort bucket's pick is compiled with its step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import init_params
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampling import greedy, sample_rows

N_SLOTS = 16


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("stablelm-1.6b").reduced(n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class RowByRow(ServingEngine):
    """The oracle: the same cohort step, each row's argmax taken and
    read on its own."""

    def _pick(self, logits, reqs):
        return jnp.asarray([int(jnp.argmax(logits[b]))
                            for b in range(logits.shape[0])], jnp.int32)


def _serve(engine_cls, cfg, params, n, temps=None, rng_seed=0):
    eng = engine_cls(cfg, params, n_slots=N_SLOTS, max_len=256,
                     rng_seed=rng_seed)
    temps = temps or [0.0] * n
    for i in range(n):
        prompt = (np.arange(6 + i) * (i + 3)) % 200 + 3
        eng.submit(Request(rid=i, tokens=prompt, max_new_tokens=6,
                           temperature=temps[i]))
    done = eng.run()
    assert len(done) == n
    return eng, {r.rid: r.out_tokens for r in done}


def _cohorts(eng):
    return [e.rid for e in eng.trace if e.event == "decode_cohort"]


@pytest.mark.parametrize("n", [1, 3, 16])
def test_batched_pick_matches_row_by_row(setup, n):
    cfg, params = setup
    eng, got = _serve(ServingEngine, cfg, params, n)
    assert n in _cohorts(eng)                  # the cohort reached n rows
    _, want = _serve(RowByRow, cfg, params, n)
    assert got == want


@pytest.mark.parametrize("n", [3, 16])
def test_one_sample_read_per_decode_step(setup, n):
    cfg, params = setup
    eng = ServingEngine(cfg, params, n_slots=N_SLOTS, max_len=256)
    for i in range(n):
        eng.submit(Request(rid=i, tokens=np.arange(5 + i) + 3,
                           max_new_tokens=5))
    while eng.queue or eng.live:
        reads, cohorts = eng.stats.sample_reads, len(_cohorts(eng))
        eng.step()
        assert eng.stats.sample_reads - reads == len(_cohorts(eng)) - cohorts
        assert len(_cohorts(eng)) - cohorts <= 1
    rows = _cohorts(eng)
    assert eng.stats.sample_reads == len(rows) > 0
    assert eng.stats.decoded_tokens == sum(rows)


def test_mixed_cohort_greedy_rows_exact_sampled_rows_seeded(setup):
    cfg, params = setup
    temps = [0.0, 0.8, 0.0, 0.8, 0.0]
    _, greedy_out = _serve(ServingEngine, cfg, params, len(temps))
    _, a = _serve(ServingEngine, cfg, params, len(temps), temps, rng_seed=7)
    _, b = _serve(ServingEngine, cfg, params, len(temps), temps, rng_seed=7)
    assert a == b
    for rid, t in enumerate(temps):
        if t == 0.0:
            assert a[rid] == greedy_out[rid]
        else:
            assert all(0 <= tok < cfg.vocab_size for tok in a[rid])


@pytest.mark.parametrize("bc", [1, 4])
def test_cohort_bucket_pick_compiled_with_step(setup, bc):
    cfg, params = setup
    eng = ServingEngine(cfg, params, n_slots=N_SLOTS, max_len=256)
    step = eng._cohort_fn(bc)
    i32 = np.int32
    logits, eng.slots.pool = step(
        params, jnp.zeros((bc, 1), i32), jnp.zeros((bc,), i32),
        jnp.full((bc,), eng.slots.n_slots, i32),
        jnp.full((bc, eng.slots.blocks_per_slot), eng.slots.n_blocks, i32),
        eng.slots.pool)
    before = eng.jit_counts()
    toks = eng._pick(logits, [Request(rid=0, tokens=np.arange(4))])
    after = eng.jit_counts()
    assert toks.shape == (bc,) and toks.dtype == jnp.int32
    assert after["compiles"] == before["compiles"]
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(
        jnp.argmax(logits, axis=-1)))


def _logits(seed, rows=6, vocab=50):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, vocab)) * 3


@pytest.mark.parametrize("seed", [0, 1])
def test_sample_rows_all_greedy_equals_greedy(seed):
    logits = _logits(seed)
    got = sample_rows(logits, jax.random.PRNGKey(seed + 10),
                      jnp.zeros((logits.shape[0],), jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(greedy(logits)))


@pytest.mark.parametrize("seed", [0, 1])
def test_sample_rows_tiny_temperature_picks_argmax(seed):
    # logits a whole unit apart: at t = 1e-4 no Gumbel draw bridges them
    logits = jnp.asarray(np.stack([np.random.default_rng(seed + r)
                                   .permutation(50) for r in range(6)]),
                         jnp.float32)
    temps = jnp.asarray([1e-4, 0.0, 1e-4, 1e-4, 0.0, 1e-4], jnp.float32)
    got = sample_rows(logits, jax.random.PRNGKey(seed), temps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(greedy(logits)))


def test_sample_rows_draws_only_hot_rows():
    logits = jnp.zeros((4, 64), jnp.float32).at[:, 5].set(0.5)
    temps = jnp.asarray([0.0, 5.0, 0.0, 5.0], jnp.float32)
    draws = np.stack([np.asarray(sample_rows(logits, jax.random.PRNGKey(s),
                                             temps)) for s in range(8)])
    assert (draws[:, [0, 2]] == 5).all()        # greedy rows never move
    assert len(set(draws[:, 1]) | set(draws[:, 3])) > 1
    assert ((draws >= 0) & (draws < 64)).all()
