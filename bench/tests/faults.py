"""Faults planted under the timed path, for the tests that see the check
fail: each patches the program for the duration of a ``with`` block."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _patched(obj, name, make):
    old = getattr(obj, name)
    setattr(obj, name, make(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """The decode step returns the decode state it was given."""
    from repro.serve.engine import ServingEngine

    def make(old):
        def _decode_all(self, params, state, tokens, positions, key):
            tok, _ = old(self, params, state, tokens, positions, key)
            return tok, state
        return _decode_all
    return _patched(ServingEngine, "_decode_all", make)


def _keep_odd_rows(old_state, new_state, axes, rows):
    """``new_state`` with every odd row of the batch put back to
    ``old_state``: those rows are left out of the update."""
    odd = jnp.arange(rows) % 2 == 1

    def keep(o, n, ax):
        if ax < 0:
            return n
        shape = [1] * n.ndim
        shape[ax] = rows
        return jnp.where(odd.reshape(shape), o, n)
    return jax.tree.map(keep, old_state, new_state, axes)


@contextlib.contextmanager
def half_batch():
    """Prefill and decode leave out every odd row of the batch: their
    state is not written (their tokens are still computed)."""
    from repro.nn.transformer import LM
    from repro.serve.engine import ServingEngine

    def make_decode(old):
        def _decode_all(self, params, state, tokens, positions, key):
            tok, new = old(self, params, state, tokens, positions, key)
            return tok, _keep_odd_rows(state, new, self._batch_axes(),
                                       tokens.shape[0])
        return _decode_all

    def make_prefill(old):
        def prefill_cache(self, params, state, tokens, valid_len, *,
                          key=None, batch_axes=None):
            new = old(self, params, state, tokens, valid_len, key=key,
                      batch_axes=batch_axes)
            return _keep_odd_rows(state, new, batch_axes, tokens.shape[0])
        return prefill_cache

    with _patched(ServingEngine, "_decode_all", make_decode), \
            _patched(LM, "prefill_cache", make_prefill):
        yield


def token_altered():
    """Every token is the one after the decode step's greedy choice."""
    from repro.serve.engine import ServingEngine

    def make(old):
        def _decode_all(self, params, state, tokens, positions, key):
            tok, new = old(self, params, state, tokens, positions, key)
            return (tok + 1) % self.model.cfg.vocab, new
        return _decode_all
    return _patched(ServingEngine, "_decode_all", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
