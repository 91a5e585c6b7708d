"""Faults planted under the timed path, for the runs that show ``correct``
coming out false (bench/tests/test_run.py, ``bench/control.py --faults``).
Each takes the harness's ``Stepper`` after set-up and breaks the engine
underneath it."""

from __future__ import annotations


def stale_state(drv):
    """Every step returns the page pool it was given: nothing is written."""
    eng = drv.eng
    step = eng._paged

    def paged(params, tokens, positions, valid, pool, *rest):
        logits, _ = step(params, tokens, positions, valid, pool, *rest)
        return logits, pool

    eng._paged = paged


def half_batch(drv):
    """Decode steps leave out the second half of the batch: its rows write
    no K/V and their logits come from rows the step did not compute."""
    eng = drv.eng
    step = eng._paged

    def paged(params, tokens, positions, valid, *rest):
        if tokens.shape[0] > 1:
            valid = valid.at[tokens.shape[0] // 2:].set(False)
        return step(params, tokens, positions, valid, *rest)

    eng._paged = paged


def altered_token(drv):
    """Every token is altered where it is produced (the next id)."""
    eng = drv.eng
    commit = eng._commit_token
    vocab = eng.cfg.vocab_size

    def commit_token(req, tok):
        commit(req, (int(tok) + 1) % vocab)

    eng._commit_token = commit_token


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "altered_token": altered_token}

