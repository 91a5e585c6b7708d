"""Batched sampling: ``sample_rows`` draws every row of a step in one
program, bitwise the token ``sample_token`` draws for that row alone under
the engine's key derivation (seed, rid, token index), with per-row
non-finite counts; and the engine calls it once per decode step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model
from repro.models.config import reduced
from repro.serve import engine as engine_lib
from repro.serve.engine import Request, RequestState, ServeEngine
from repro.serve.sampling import (NonFiniteLogitsError, non_finite_error,
                                  sample_rows, sample_rows_packed,
                                  sample_token)

TEMPS = (0.0, 0.5, 1.0, 2.0)


def _key(base_key, rid, idx):
    return jax.random.fold_in(jax.random.fold_in(base_key, int(rid)), int(idx))


def _per_row(logits, base_key, rids, idx, temps):
    """The per-row spelling: one ``sample_token`` call per row (of a step's
    (B, S, V) logits, at the last position)."""
    if logits.ndim == 3:
        logits = logits[:, -1]
    return np.asarray([
        int(sample_token(logits[b:b + 1], _key(base_key, rids[b], idx[b]),
                         temperature=float(temps[b]))[0])
        for b in range(logits.shape[0])], np.int32)


def _batch(rows, vocab, seed):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(rows, vocab)) * 4, jnp.float32)
    rids = rng.integers(0, 2**32, rows, dtype=np.uint32)
    idx = rng.integers(0, 768, rows).astype(np.uint32)
    temps = np.asarray([TEMPS[b % len(TEMPS)] for b in range(rows)],
                       np.float32)
    rng.shuffle(temps)
    return logits, jax.random.PRNGKey(seed), rids, idx, temps


@pytest.mark.parametrize("rows", [1, 8, 64])
@pytest.mark.parametrize("vocab", [49152, 37])
def test_sample_rows_matches_per_row_sample_token(rows, vocab):
    logits, base_key, rids, idx, temps = _batch(rows, vocab, rows + vocab)
    toks, n_nan, n_inf = jax.device_get(
        sample_rows(logits, base_key, rids, idx, temps))
    assert toks.dtype == np.int32 and toks.shape == (rows,)
    np.testing.assert_array_equal(
        toks, _per_row(logits, base_key, rids, idx, temps))
    assert not n_nan.any() and not n_inf.any()
    # a step's (B, S, V) logits are sampled at each row's last position,
    # and the packed entry is the same program
    steps = jnp.concatenate([jnp.flip(logits, 0)[:, None], logits[:, None]], 1)
    np.testing.assert_array_equal(jax.device_get(
        sample_rows(steps, base_key, rids, idx, temps)[0]), toks)
    packed = np.stack([rids, idx, temps.view(np.uint32)], 1)
    np.testing.assert_array_equal(
        jax.device_get(sample_rows_packed(steps, base_key, packed)),
        np.stack([toks, n_nan, n_inf]))
    if rows > 1:
        # keys, not positions, decide a row's token: permuting the batch
        # permutes the tokens
        perm = np.random.default_rng(rows).permutation(rows)
        ptoks, _, _ = jax.device_get(sample_rows(
            logits[perm], base_key, rids[perm], idx[perm], temps[perm]))
        np.testing.assert_array_equal(ptoks, toks[perm])


@pytest.mark.parametrize("vocab", [49152, 37])
def test_sample_rows_counts_non_finite_per_row(vocab):
    logits, base_key, rids, idx, temps = _batch(8, vocab, 3)
    bad = (logits.at[1, ::7].set(jnp.nan)
           .at[4, ::5].set(jnp.inf)
           .at[6, 0].set(jnp.nan).at[6, 1].set(-jnp.inf))
    toks, n_nan, n_inf = jax.device_get(
        sample_rows(bad, base_key, rids, idx, temps))
    np.testing.assert_array_equal(n_nan, np.isnan(np.asarray(bad)).sum(-1))
    np.testing.assert_array_equal(n_inf, np.isinf(np.asarray(bad)).sum(-1))
    assert set(np.flatnonzero(n_nan + n_inf)) == {1, 4, 6}
    # clean rows beside the planted ones draw what they draw alone
    clean, _, _ = jax.device_get(
        sample_rows(logits, base_key, rids, idx, temps))
    for b in (0, 2, 3, 5, 7):
        assert toks[b] == clean[b]
    # a planted row's error is the one sample_token raises for it
    for b in (1, 4, 6):
        with pytest.raises(NonFiniteLogitsError) as err:
            sample_token(bad[b:b + 1], _key(base_key, rids[b], idx[b]),
                         temperature=float(temps[b]), check_finite=True)
        assert str(err.value) == str(non_finite_error(
            "sampling", int(n_nan[b]), int(n_inf[b]), vocab))


@pytest.mark.parametrize("arch,mode", [("smollm-135m", "paged"),
                                       ("mamba2-370m", "stacked")])
def test_engine_samples_each_decode_step_in_one_call(monkeypatch, rng, arch,
                                                     mode):
    """One sampling call per decode step whatever the number of active
    rows, counted by ``sample_calls``; each call's tokens are the per-row
    ``sample_token`` ones, so the served streams are too."""
    cfg = reduced(get_config(arch))
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    slots = 3
    decode_calls = []

    def checked(logits, base_key, rows):
        out = sample_rows_packed(logits, base_key, rows)
        rids, idx, temps = rows[:, 0], rows[:, 1], rows[:, 2].view(np.float32)
        if logits.shape[0] == slots:
            rows = [i for i, r in enumerate(eng.slot_req)
                    if r is not None and r.state is RequestState.DECODING]
            decode_calls.append(len(rows))
            # each decoding row under its request's key and temperature
            for i in rows:
                req = eng.slot_req[i]
                assert (rids[i], idx[i], temps[i]) == (
                    req.rid, len(req.out_tokens), req.temperature)
            np.testing.assert_array_equal(
                np.asarray(out[0]),
                _per_row(logits, base_key, rids, idx, temps))
        return out

    monkeypatch.setattr(engine_lib, "sample_rows_packed", checked)
    eng = ServeEngine(cfg, params, batch_slots=slots, max_seq=32)
    assert eng.mode == mode
    # lengths and temperatures differ, so steps run 3, 2 and 1 rows
    for rid, (n, temp) in enumerate([(3, 0.0), (7, 0.7), (5, 1.5),
                                     (4, 0.0)]):
        eng.submit(Request(
            rid=rid, temperature=temp, max_new_tokens=n,
            prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32)))
    done = eng.run()
    assert all(done[r].ok for r in range(4))
    counters = eng.health()["counters"]
    assert counters["sample_calls"] == counters["decode_calls"] == len(
        decode_calls)
    assert len(set(decode_calls)) > 1
