"""Model factory: one entry point for every assigned architecture.

Also home of the family-agnostic **cache-writing prefill** entry point
(:func:`prefill_cache`): every model family exposes its autoregressive
update through ``decode_step`` / ``init_decode_state``, so one masked scan
over that seam fills decode caches for a *batch* of prompts of different
lengths in fixed-size chunks — the building block of the serving engine's
bucketed/packed prefill path.  Families can override it with a method of
the same name; :class:`~repro.nn.transformer.LM` and
:class:`~repro.nn.encdec.EncDecLM` delegate here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def build(cfg: ModelConfig):
    """Return the model object (LM / EncDecLM) for a config."""
    if cfg.family == "encdec":
        from repro.nn.encdec import EncDecLM

        return EncDecLM(cfg)
    if cfg.family == "lstm":
        raise ValueError(
            "LSTM workloads use repro.nn.lstm directly (see examples/)")
    from repro.nn.transformer import LM

    return LM(cfg)


# ---------------------------------------------------------------------------
# Cache-writing chunked prefill over the decode seam
# ---------------------------------------------------------------------------


def decode_state_batch_axes(model):
    """Per-leaf batch axis of ``model.init_decode_state``'s pytree.

    Found structurally — build the state for two batch sizes under
    ``eval_shape`` (no allocation) and see which dimension moved.  Leaves
    without a batch dimension (the shared ``index`` scalar) map to ``-1``
    (``None`` would vanish as pytree structure).  The result is what
    :func:`prefill_cache` and the engine's slot scatter need to mask or
    route per-request rows through an otherwise shared state tree.
    """
    s1 = jax.eval_shape(lambda: model.init_decode_state(1, 9))
    s2 = jax.eval_shape(lambda: model.init_decode_state(2, 9))

    def one(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if not diffs:
            return -1
        if len(diffs) > 1:
            raise ValueError(
                f"decode-state leaf {a.shape} -> {b.shape} changes more "
                "than one dimension with batch size; cannot infer the "
                "batch axis")
        return diffs[0]

    return jax.tree.map(one, s1, s2)


def prefill_cache(model, params, state, tokens, valid_len, *, key=None,
                  batch_axes=None):
    """Advance ``state`` over a fixed-size token chunk, batched per row.

    ``tokens``: ``(P, B)`` int32 — ``B`` prompt positions for ``P``
    requests, zero-padded past each row's length.  ``valid_len``: ``(P,)``
    int32 — each row's TOTAL prefill length (global, so chunked calls keep
    passing the same value).  Rows commit a step's update only while
    ``state["index"] + t < valid_len``; masked steps compute and discard
    (pure), so the committed leaves are **bitwise** what a row-at-a-time
    scan over ``decode_step`` would have written — exact by construction,
    including rolling windows and int8 KV layouts, because it IS the decode
    path.  Chunking falls out of the shared ``index``: call again with the
    next ``B`` columns and the scan resumes at the global position.

    A model that ``commits_rows`` (latent attention) takes the mask into
    ``decode_step`` (``commit``) instead, so that no step copies the whole
    cache to select rows: masked rows write zeros at their position, which
    is what the fresh state the engine prefills holds there.

    ``key``: one noise key for the whole wave (all rows of one prefill
    call read the same physical chip instance — noise draws are weight-
    and threshold-shaped, never batch-shaped); per-step keys are derived
    by ``fold_in(key, global_position)`` so the schedule is independent of
    both the chunk decomposition and the padded chunk width.
    """
    if batch_axes is None:
        batch_axes = decode_state_batch_axes(model)
    tokens = jnp.asarray(tokens, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    n_rows = tokens.shape[0]
    i0 = state["index"]
    commits = getattr(model, "commits_rows", False)

    def body(st, inp):
        t, tok = inp                          # t scalar, tok (P,)
        pos = i0 + t
        k = None if key is None else jax.random.fold_in(key, pos)
        take = pos < valid_len                # (P,) per-row commit mask
        if commits:
            _, new = model.decode_step(params, st, tok[:, None], key=k,
                                       commit=take)
            return new, None
        _, new = model.decode_step(params, st, tok[:, None], key=k)

        def sel(old, fresh, ax):
            if ax < 0:
                return fresh                  # shared leaves always advance
            shape = [1] * fresh.ndim
            shape[ax] = n_rows
            return jnp.where(jnp.reshape(take, shape), fresh, old)

        return jax.tree.map(sel, st, new, batch_axes), None

    state, _ = jax.lax.scan(
        body, state, (jnp.arange(tokens.shape[1]), tokens.T))
    return state
