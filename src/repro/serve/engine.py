"""Batched serving engine: continuous batching over a fixed decode batch.

Production shape (vLLM-style, sized down to JAX-native primitives):

* a fixed ``(max_batch, max_len)`` decode state (KV caches / recurrent
  states) allocated once;
* incoming requests queue; free slots are **prefilled** (forward over the
  prompt while writing the slot's cache) and then join the decode batch;
* one ``decode_step`` advances *all* active slots a token (continuous
  batching); finished slots (EOS / max_tokens) free immediately;
* per-slot position offsets let requests of different lengths coexist.

Two prefill paths share one correctness anchor (bitwise-identical token
streams and decode caches, tested on both backends, noisy and noiseless):

* ``prefill="scan"`` (default) — the legacy path: per-request jitted
  ``lax.scan`` over ``decode_step`` (exact w.r.t. the cache layout,
  including rolling windows), one compile per distinct prompt length.
* ``prefill="bucketed"`` — the MLPerf-offline-style throughput path:
  **power-of-two prefill length buckets**, each an **AOT-compiled
  executable** (``jax.jit(...).lower(...).compile()``) built once and
  reused for every prompt that rounds up into the bucket; ``warmup()``
  pre-compiles every bucket and the decode step before traffic arrives.
  With ``pack_prefill=True`` one padded prefill call carries the whole
  admission wave (several short prompts batched into the pack rows, each
  masked to its own length) and the resulting caches **scatter** into
  their batch slots — generalizing the single-slot ``_merge_slot``.
  Prompts longer than the largest bucket run **chunked**: repeated
  largest-bucket calls carrying the state, the shared ``index`` keeping
  cache positions and the noise-key schedule global.

Attention inside both paths dispatches through the kernel layer: each
``decode_step`` (and therefore every prefill position, since prefill is a
masked scan of decode steps) attends over the cache via
``backend.prefill_attention`` — the Pallas cached-attention kernel under
``REPRO_ANALOG_BACKEND=pallas``, ``attend_full`` on the ref backend —
with block sizes resolved per shape from the :mod:`repro.kernels.tune`
cache.

``detok_thread=True`` moves argmax→host transfer→request bookkeeping onto
a background detokenize/backlog thread: the next device step dispatches
against a device-side last-token vector while the previous step's tokens
land asynchronously (results lag up to one ``step``; ``detok_flush``
joins the backlog — checkpoints do it automatically).

Inside the decode step the attention/recurrence primitives dispatch
through the model's configured analog backend (``AnalogConfig.backend``)
— with ``kv_cache_dtype="int8"`` and ``backend="pallas"`` the batched
decode hot loop runs the fused flash-decode kernel.
"""

from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as otrace


def serving_params(model, key):
    """``model.init(key)`` as the serve path stores it: float32 leaves in
    the config's ``serve_params_dtype``.  Init and cast are one jit, so the
    float32 tree is never whole in device memory (qwen2.5-3b: 12.4 GB of
    a v5e's 16 GB, and XLA adds bf16 copies of whole layer stacks ahead
    of the layer loop when the weights are float32)."""
    dt = jnp.dtype(model.cfg.serve_params_dtype)

    def init(k):
        return jax.tree.map(
            lambda a: a.astype(dt) if a.dtype == jnp.float32 else a,
            model.init(k))

    return jax.jit(init)(key)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (len,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                    # -1: never
    # filled by the engine
    generated: Optional[List[int]] = None
    # when the request was due, on the time.perf_counter clock (None:
    # when it was submitted); serve.ttft_ms counts from it.  Process-
    # local, so checkpoints leave it out.
    arrival_s: Optional[float] = None

    def to_dict(self) -> dict:
        return {"uid": self.uid, "prompt": np.asarray(self.prompt).tolist(),
                "max_new_tokens": self.max_new_tokens, "eos_id": self.eos_id,
                "generated": list(self.generated or [])}

    @classmethod
    def from_dict(cls, d: dict) -> "Request":
        return cls(uid=d["uid"],
                   prompt=np.asarray(d["prompt"], np.int32),
                   max_new_tokens=d["max_new_tokens"], eos_id=d["eos_id"],
                   generated=list(d["generated"]))


class _DetokWorker:
    """Background detokenize/backlog pipeline.

    The engine hands each decode step's device token vector plus a
    snapshot of the active ``(slot, request)`` pairs to this thread; the
    thread performs the device→host transfer (``np.asarray`` blocks on the
    computation — off the dispatch path) and the per-request bookkeeping
    (append to ``generated``, EOS detection), so the next device step
    launches without waiting for the previous step's host work.

    Ordering is preserved (one FIFO queue, one worker), so ``generated``
    streams are bitwise what the synchronous path appends.  EOS detection
    necessarily lags one step: the slot is reaped at the top of the *next*
    engine step, and the worker stops appending past the EOS token so the
    stream itself stays truncated exactly like the synchronous path.
    """

    def __init__(self):
        self._q: _queue.Queue = _queue.Queue()
        self._results: _queue.Queue = _queue.Queue()
        self._lock = threading.Lock()
        self._eos: List[Tuple[int, int]] = []      # (slot, uid)
        self._thread = threading.Thread(
            target=self._loop, name="serve-detok", daemon=True)
        self._thread.start()

    def put(self, next_tok, snapshot, pairs=None, on_pairs=None) -> None:
        """Enqueue one decode step's device tokens + active-slot snapshot
        (and a MoE model's per-row pair counts, handed to ``on_pairs``
        with the active slots once they land)."""
        self._q.put((next_tok, snapshot, pairs, on_pairs))

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            next_tok, snapshot, pairs, on_pairs = item
            toks = np.asarray(next_tok)            # device -> host, here
            if pairs is not None:
                on_pairs(np.asarray(pairs), [slot for slot, _ in snapshot])
            out = {}
            for slot, req in snapshot:
                if getattr(req, "_eos_seen", False):
                    continue                       # truncate past EOS
                tok = int(toks[slot])
                req.generated.append(tok)
                out[req.uid] = tok
                if req.eos_id >= 0 and tok == req.eos_id:
                    req._eos_seen = True
                    with self._lock:
                        self._eos.append((slot, req.uid))
            self._results.put(out)
            self._q.task_done()

    def take_eos(self) -> List[Tuple[int, int]]:
        """Slots whose request hit EOS since the last call (one-step lag)."""
        with self._lock:
            out, self._eos = self._eos, []
        return out

    def pop_one(self) -> Dict[int, int]:
        """At most one landed step batch (non-blocking; {} if none yet)."""
        try:
            return self._results.get_nowait()
        except _queue.Empty:
            return {}

    def flush(self) -> List[Dict[int, int]]:
        """Block until the backlog is processed; return the landed batches."""
        self._q.join()
        out = []
        while True:
            try:
                out.append(self._results.get_nowait())
            except _queue.Empty:
                return out


class ServingEngine:
    """``device``: an optional :class:`repro.core.device.DeviceModel` whose
    build stage (per-chip write noise, stuck faults, retention drift — drawn
    once, host-side, **per crossbar tile** keyed by the TilePlan) is applied
    to the weight matrices at engine construction, simulating serving from
    an actually-programmed chip.  The step-time stages (read noise,
    programmed NL-ADC ramps) ride on the model's ``AnalogConfig`` as usual.
    The caller decides when aging composes with the model's analog mode
    (``launch.serve`` passes a device only in ``mode="infer"`` — aged
    weights with a pristine NL-ADC would be a chip that cannot exist).

    ``recal``: an optional :class:`repro.serve.lifecycle.RecalPolicy`.
    With one, the engine owns a :class:`RecalScheduler` that advances device
    age every :meth:`step`, probes deployed-ramp INL on the policy cadence,
    triggers one-point re-calibration past the threshold, re-ages the
    weight crossbars to the current age, and re-jits (reprogramming the
    chip invalidates the compiled step's threshold constants).

    The whole deployment — aged params, programmed ramps (including the
    per-col-tile threshold banks), scheduler clock, noise-key schedule,
    decode caches, in-flight requests — checkpoints via :meth:`save`
    (schema version ``SCHEMA``) and resumes bit-identically via
    :meth:`restore` (older schemas migrate; unknown ones are rejected with
    an upgrade hint).

    ``drain_before_rejit``: scheduler-aware continuous batching.  When a
    chip re-program lands mid-wave, the engine stops admitting, lets the
    in-flight decode slots finish on the already-compiled step (the old
    chip — physically, the re-program is deferred), and only then
    re-programs and re-jits.  Off (default), the re-program applies
    immediately, recompiling mid-wave.

    ``external_maintenance``: fleet mode.  A due chip re-program does NOT
    apply on its own schedule — the engine only raises
    :attr:`maintenance_pending` and keeps serving (and admitting) on the
    already-compiled traces until an external planner
    (:class:`repro.serve.fleet.FleetEngine`) calls :meth:`begin_drain`,
    which stops admission and lets the standard drain point apply the
    re-program.  This is how a fleet staggers maintenance windows so
    capacity never drops below its floor.
    """

    SCHEMA = 2          # checkpoint schema this build writes/understands

    def __init__(self, model, params, *, max_batch: int, max_len: int,
                 device=None, noise_seed: int = 0, recal=None,
                 drain_before_rejit: bool = False,
                 external_maintenance: bool = False,
                 prefill: str = "scan",
                 prefill_buckets=None,
                 pack_prefill: bool = False,
                 detok_thread: bool = False,
                 obs=None):
        from repro.obs import ChipEnergyModel, EnergyMeter, Obs
        from repro.serve.lifecycle import RecalScheduler, analog_activations

        if prefill not in ("scan", "bucketed"):
            raise ValueError(
                f"prefill must be 'scan' or 'bucketed', got {prefill!r}")
        if prefill != "bucketed" and (pack_prefill
                                      or prefill_buckets is not None):
            raise ValueError(
                "pack_prefill / prefill_buckets require prefill='bucketed'")

        self.device = device
        self._pristine_params = params
        self._acts = analog_activations(model)
        self.scheduler = None
        self.drain_before_rejit = drain_before_rejit
        self.external_maintenance = external_maintenance
        self._rejit_pending = False
        self._maint_pending = False
        # Weight-crossbar re-program bookkeeping (probe-driven refresh):
        # generation salts the tile draws, prog-age anchors the drift clock.
        # A refresh scoped to the stalled banks' col-tiles (the per-tile
        # path) lands in _tile_gens instead of bumping the chip-wide
        # generation; _refresh_ord is the shared ordinal keeping every
        # re-program's rng salt unique across both paths.
        self._weight_gen = 0
        self._weight_prog_age_s = 0.0
        self._refresh_ord = 0
        self._tile_gens: Dict[str, dict] = {}
        if recal is not None:
            if device is None:
                raise ValueError("recal policy requires a device model")
            # The scheduler re-programs the ramps (fab calibration at age 0,
            # then drift to the preset's age) before the jits below bake
            # thresholds in.
            self.scheduler = RecalScheduler(device, self._acts, recal)
        if device is not None and device.has_build_stage:
            params = device.age_params(params)
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.state = model.init_decode_state(max_batch, max_len)
        # Infer-mode models draw per-read noise (the device model's
        # ReadNoise stage) every decode/prefill step; the engine owns the
        # key schedule so serving is reproducible for a given noise_seed.
        # Exact-mode models (and bare test doubles without a cfg) get
        # key=None — byte-identical traces to the pre-noise engine.
        spec = getattr(getattr(model, "cfg", None), "analog", None)
        self._noisy = spec is not None and spec.mode == "infer" \
            and spec.enabled
        self._noise_key = jax.random.PRNGKey(noise_seed)
        # engine bookkeeping (host side)
        self.slot_free = [True] * max_batch
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)     # next position
        self.slot_last = np.zeros(max_batch, np.int32)    # last token
        self.queue: List[Request] = []
        # -- throughput path: bucketed AOT prefill / packing / detokenize --
        self.prefill_mode = prefill
        self.pack_prefill = bool(pack_prefill)
        self._pack_rows = max_batch if pack_prefill else 1
        if prefill == "bucketed":
            buckets = tuple(int(b) for b in (
                prefill_buckets if prefill_buckets is not None
                else self._default_buckets(max_len)))
            if not buckets or any(b <= 0 for b in buckets) \
                    or list(buckets) != sorted(set(buckets)):
                raise ValueError(
                    f"prefill_buckets must be strictly increasing positive "
                    f"lengths, got {buckets}")
            self.prefill_buckets: tuple = buckets
        else:
            self.prefill_buckets = ()
        self._prefill_exec: Dict[int, object] = {}   # bucket -> executable
        self._exec_fp: Dict[int, tuple] = {}         # bucket -> thresholds
        self._batch_axes_cache = None
        self._pack_tmpl = None
        self.last_invalidation: Optional[dict] = None
        # detokenize pipeline: per-slot emitted-token counters replace
        # len(generated) for the done-check (the worker owns `generated`),
        # and the decode input comes from a device-side last-token vector
        # so the next step never waits on the previous step's host landing
        self._slot_ntok = np.zeros(max_batch, np.int64)
        self._detok = _DetokWorker() if detok_thread else None
        self._slot_last_dev = jnp.asarray(self.slot_last, jnp.int32) \
            if detok_thread else None
        # -- observability (repro.obs): tracer + metrics + energy ----------
        # The step clock: ordinal of the next step() call.  Everything the
        # obs layer records is keyed on it (never on wall time), which is
        # what makes seeded traces bitwise-reproducible; checkpointed so a
        # restored deployment continues the clock, not restarts it.
        self.obs = obs if obs is not None else Obs(trace=False)
        self._step_ord = 0
        self._submit_ord: Dict[int, int] = {}       # uid -> submit step
        self._submit_wall: Dict[int, float] = {}
        self._slot_last_tok_ord = np.zeros(max_batch, np.int64)
        self._slot_last_tok_wall = np.zeros(max_batch, np.float64)
        o = self.obs
        self._m_tokens = o.counter("serve.tokens_total")
        self._m_submitted = o.counter("serve.requests_submitted")
        self._m_admitted = o.counter("serve.requests_admitted")
        self._m_finished = o.counter("serve.requests_finished")
        self._m_queue_wait = o.histogram("serve.queue_wait_steps")
        self._m_ttft = o.histogram("serve.ttft_steps")
        self._m_itl = o.histogram("serve.itl_steps")
        self._m_ttft_ms = o.histogram("serve.ttft_ms")
        self._m_itl_ms = o.histogram("serve.itl_ms")
        self._m_bucket_hit = o.counter("serve.prefill_bucket_hits")
        self._m_bucket_compile = o.counter("serve.prefill_bucket_compiles")
        self._m_reprograms = o.counter("serve.reprograms")
        self._m_buckets_dropped = o.counter("serve.prefill_buckets_dropped")
        self._m_decode_rebuilds = o.counter("serve.decode_rebuilds")
        otrace.watch_compiles(o.metrics)
        # Per-chip energy: price the served params under both peripheries
        # (NL-ADC vs digital-LUT baseline); counters accumulate per
        # processed token so run_offline / fleet sweeps report tok/J.
        self.energy = EnergyMeter(
            ChipEnergyModel.price(
                self.params,
                bits=spec.adc_bits if spec is not None else 5,
                bank_cols=spec.bank_cols if spec is not None else 0,
                redundancy=getattr(getattr(device, "redundancy", None),
                                   "n_copies", 1)),
            o.metrics, chip=o.chip)
        if self.scheduler is not None:
            self.scheduler.obs = self.obs
        self._refresh_jit()

    def _refresh_jit(self):
        """(Re-)build the jitted step closures.

        NL-ADC thresholds are closure constants, so any chip re-program
        (scheduler redeploy, checkpoint restore) must drop the old traces.
        The snapshot taken here is the chip the new traces will SERVE —
        during a drain window (``drain_before_rejit``) the scheduler may
        move the host-side thresholds ahead of the still-compiled step, and
        a checkpoint must record what is being served, not what is pending.
        """
        self._jit_decode = jax.jit(self._decode_all)
        self._jit_prefill = jax.jit(self._prefill_slot,
                                    static_argnames=("length",))
        self._prefill_exec.clear()
        self._exec_fp.clear()
        self._served_ramps = {name: np.asarray(act.ramp.thresholds).copy()
                              for name, act in self._acts.items()}
        self._served_banks = {
            name: {width: bank.thresholds_f64.copy()
                   for width, bank in act.banks().items()}
            for name, act in self._acts.items()}

    def _served_bank_state(self):
        """Per-act served bank thresholds, including banks realized lazily
        inside the current traces (those serve their deploy-time state,
        which is their current state until the next re-jit)."""
        out = {}
        for name, act in self._acts.items():
            snap = self._served_banks.get(name, {})
            banks = {width: snap.get(width, bank.thresholds_f64)
                     for width, bank in act.banks().items()}
            if banks:
                out[name] = banks
        return out

    # -- bucketed AOT prefill ------------------------------------------

    @staticmethod
    def _default_buckets(max_len: int) -> tuple:
        """Power-of-two prefill lengths 8, 16, ... capped by the longest
        legal prefill (``max_len - 1``), which terminates the ladder so
        in-range prompts never need chunking."""
        top = max(max_len - 1, 1)
        out, b = [], 8
        while b < top:
            out.append(b)
            b *= 2
        out.append(top)
        return tuple(out)

    def _bucket_for(self, length: int) -> int:
        """Smallest bucket covering ``length`` (largest bucket if none
        does — the caller then chunks)."""
        for b in self.prefill_buckets:
            if b >= length:
                return b
        return self.prefill_buckets[-1]

    def _batch_axes(self):
        """Per-leaf batch axis of the decode-state tree (cached; -1 for
        shared leaves) — drives both the pack-row length masking and the
        row->slot scatter."""
        if self._batch_axes_cache is None:
            from repro.nn.model import decode_state_batch_axes

            self._batch_axes_cache = decode_state_batch_axes(self.model)
        return self._batch_axes_cache

    def _pack_template(self):
        """The fresh (all-zero) pack-rows decode state every prefill wave
        starts from.  Never mutated (executables return new arrays), so
        one allocation serves the engine's lifetime."""
        if self._pack_tmpl is None:
            self._pack_tmpl = self.model.init_decode_state(
                self._pack_rows, self.max_len)
        return self._pack_tmpl

    def _prefill_packed(self, params, state, tokens, valid_len, key):
        """Jittable body of one bucket executable: the model's cache-
        writing prefill (masked scan over the decode seam — exact by
        construction, see :func:`repro.nn.model.prefill_cache`)."""
        fn = getattr(self.model, "prefill_cache", None)
        if fn is None:
            from repro.nn.model import prefill_cache

            return prefill_cache(self.model, params, state, tokens,
                                 valid_len, key=key,
                                 batch_axes=self._batch_axes())
        return fn(params, state, tokens, valid_len, key=key,
                  batch_axes=self._batch_axes())

    def _ensure_prefill_exec(self, bucket: int):
        """The AOT-compiled executable for one bucket length.

        Compiled once (``jax.jit(...).lower(...).compile()``) and reused
        for every wave that rounds up into the bucket; invalidated only
        when a chip re-program moves the thresholds its trace baked in
        (see :meth:`_refresh_jit_selective`).
        """
        ex = self._prefill_exec.get(bucket)
        if ex is not None:
            self._m_bucket_hit.inc()
            return ex
        self._m_bucket_compile.inc()
        P = self._pack_rows
        tokens = jnp.zeros((P, bucket), jnp.int32)
        vlen = jnp.zeros((P,), jnp.int32)
        key = self._noise_key if self._noisy else None
        with otrace.annotate(otrace.SERVE_COMPILE):
            ex = jax.jit(self._prefill_packed).lower(
                self.params, self._pack_template(), tokens, vlen,
                key).compile()
        self._prefill_exec[bucket] = ex
        # fingerprint AFTER compiling: the trace may have realized
        # threshold banks lazily, and those are part of what it serves
        self._exec_fp[bucket] = self._threshold_fp()
        return ex

    def warmup(self) -> dict:
        """Pre-compile every prefill bucket executable, the slot scatter
        and the decode step before traffic arrives (MLPerf-offline style:
        compile time is paid here, not inside the measured burst)."""
        out = {"prefill_buckets": [], "decode": True}
        with otrace.annotate(otrace.SERVE_WARMUP):
            for b in self.prefill_buckets:
                self._ensure_prefill_exec(b)
                out["prefill_buckets"].append(b)
            if self.prefill_buckets:
                # a scatter of no rows: compiles its ops, moves nothing
                self._scatter_rows(self._pack_template(), [])
            # one representative-shape decode call triggers (and caches)
            # the jit compile; the result is discarded and no engine
            # state — in particular the noise-key schedule — advances.
            # Waiting for it leaves nothing of the warm-up queued on the
            # device, holding memory, when traffic starts.
            tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
            positions = jnp.zeros((self.max_batch,), jnp.int32)
            key = None
            if self._noisy:
                key = self._noise_key
                jax.random.split(key)     # the key schedule's own program
            with otrace.annotate(otrace.SERVE_COMPILE):
                jax.block_until_ready(self._jit_decode(
                    self.params, self.state, tokens, positions, key))
        return out

    # -- threshold fingerprints (bucket-aware invalidation) ------------

    def _threshold_fp(self) -> tuple:
        """Bytes-level fingerprint of every deployed comparator threshold
        (shared ramps + realized per-col-tile banks) — exactly the
        constants a trace bakes in."""
        fp = []
        for name in sorted(self._acts):
            act = self._acts[name]
            banks = act.banks()
            fp.append((name, np.asarray(act.ramp.thresholds).tobytes(),
                       tuple((w, banks[w].thresholds_f64.tobytes())
                             for w in sorted(banks))))
        return tuple(fp)

    def _served_fp(self) -> tuple:
        """The fingerprint the *currently compiled* decode/legacy-prefill
        traces serve (their snapshot, not the host-side activations —
        during a drain window the two differ)."""
        banks_all = self._served_bank_state()
        fp = []
        for name in sorted(self._acts):
            banks = banks_all.get(name, {})
            fp.append((name,
                       np.asarray(self._served_ramps[name]).tobytes(),
                       tuple((w, np.asarray(banks[w]).tobytes())
                             for w in sorted(banks))))
        return tuple(fp)

    def _refresh_jit_selective(self):
        """Bucket-aware re-jit after a chip re-program.

        Drops (and eagerly re-AOTs) only the bucket executables whose
        traced thresholds actually moved, and keeps the decode /
        legacy-prefill traces when no threshold did — a weight-only
        re-program passes params as runtime arguments, so its traces
        still serve the current chip.  A recal storm therefore no longer
        throws away every compiled prefill.  What happened lands in
        ``last_invalidation`` (the fleet surfaces it on
        ``reprogram_done`` events).
        """
        new_fp = self._threshold_fp()
        warm = sorted(self._prefill_exec)
        dropped = sorted(b for b, fp in self._exec_fp.items()
                         if fp != new_fp)
        kept = [b for b in warm if b not in dropped]
        decode_rebuilt = self._served_fp() != new_fp
        if decode_rebuilt:
            keep_exec = {b: self._prefill_exec[b] for b in kept}
            keep_fp = {b: self._exec_fp[b] for b in kept}
            self._refresh_jit()
            self._prefill_exec.update(keep_exec)
            self._exec_fp.update(keep_fp)
        else:
            for b in dropped:
                del self._prefill_exec[b]
                del self._exec_fp[b]
        for b in dropped:
            # it was warm before the re-program — re-AOT now so the next
            # admission wave doesn't pay the compile on the serving path
            self._ensure_prefill_exec(b)
        self.last_invalidation = {
            "kept_buckets": kept, "dropped_buckets": dropped,
            "decode_rebuilt": bool(decode_rebuilt)}
        self._m_reprograms.inc()
        self._m_buckets_dropped.inc(len(dropped))
        if decode_rebuilt:
            self._m_decode_rebuilds.inc()
        self.obs.trace_event("reprogram", kept_buckets=kept,
                             dropped_buckets=dropped,
                             decode_rebuilt=bool(decode_rebuilt))

    def _next_key(self):
        if not self._noisy:
            return None
        self._noise_key, k = jax.random.split(self._noise_key)
        return k

    # -- jitted bodies -------------------------------------------------

    def _decode_all(self, params, state, tokens, positions, key):
        """Advance every slot one token (positions vary per slot)."""
        # The model decode_step uses a single shared index; per-slot offsets
        # are handled by keeping a per-slot position and passing the max —
        # cache writes use the per-slot position via the index trick below.
        logits, new_state = self.model.decode_step(params, state, tokens,
                                                   key=key)
        # greedy over the real vocabulary: the columns that pad the
        # embedding table to vocab_pad_multiple are not tokens
        next_tok = jnp.argmax(logits[:, -1, :self.model.cfg.vocab],
                              axis=-1).astype(jnp.int32)
        return next_tok, new_state

    def _prefill_slot(self, params, state, tokens, key, *, length: int):
        """Feed a prompt through decode steps to fill the cache (exact).

        Per-step noise keys fold the admission wave's key at the absolute
        prompt position (``fold_in(key, t)``) — length-independent, and
        the same schedule the bucketed/packed executables derive from the
        global index, which is what makes the two prefill paths bitwise
        interchangeable under noise.
        """

        def body(st, inp):
            t, tok = inp
            k = None if key is None else jax.random.fold_in(key, t)
            _, st = self.model.decode_step(params, st, tok[None, None],
                                           key=k)
            return st, None

        # note: fills batch slot 0 of a broadcast state; engine embeds the
        # single-request state into the big batch after (host-side gather).
        state, _ = jax.lax.scan(
            body, state, (jnp.arange(length), tokens[:length]))
        return state

    # -- host-side scheduling -------------------------------------------

    def submit(self, req: Request):
        req.generated = []
        self.queue.append(req)
        self._submit_ord[req.uid] = self._step_ord
        self._submit_wall[req.uid] = time.perf_counter() \
            if req.arrival_s is None else req.arrival_s
        self._m_submitted.inc()
        self.obs.trace_event("submit", uid=req.uid,
                             prompt_len=int(len(req.prompt)))

    # -- fleet-facing maintenance surface --------------------------------

    @property
    def maintenance_pending(self) -> bool:
        """True while a chip re-program is due or draining toward one."""
        return self._maint_pending or self._rejit_pending

    @property
    def draining(self) -> bool:
        """True once drain started: admission is closed until the re-jit."""
        return self._rejit_pending

    def begin_drain(self) -> None:
        """Grant the pending maintenance window: stop admitting, let the
        in-flight wave finish on the old chip, then re-program + re-jit at
        the standard drain point (top of :meth:`step`).  Queued requests
        should be handed to siblings via :meth:`take_queue` first."""
        self._rejit_pending = True

    def take_queue(self) -> List[Request]:
        """Pop every queued (not yet prefilled) request for sibling
        handoff — in-flight slots always finish on this chip."""
        out, self.queue = self.queue, []
        return out

    def health(self) -> dict:
        """Cheap health snapshot for routing/planning (no fresh probes —
        INL comes from the scheduler's last recorded event)."""
        sched = self.scheduler
        ev = {}
        if sched is not None and sched.events:
            ev = sched.events[-1]
        return {
            "active": int(sum(not f for f in self.slot_free)),
            "queued": len(self.queue),
            "free_slots": int(sum(self.slot_free)),
            "age_s": 0.0 if sched is None else float(sched.age_s),
            "inl_lsb": float(ev.get("inl_after_lsb",
                                    ev.get("inl_lsb", 0.0))),
            # probe freshness: engine steps since the INL above was
            # recorded (-1: never probed) + the probe cadence, so routers
            # can discount a health number that has gone stale
            "inl_age_steps": int(sched.step_count - ev["step"]) if ev
            else -1,
            "check_every": 0 if sched is None
            else int(sched.policy.check_every),
            "maintenance_pending": self.maintenance_pending,
            "draining": self.draining,
            "weight_gen": self._weight_gen,
        }

    def _admit(self):
        """Prefill queued requests into free slots.

        One noise key per admission wave (drawn iff any admitted prompt
        actually prefills), shared by every request admitted together —
        all reads of one wave see the same physical chip instance, and
        noise draws are weight-/threshold-shaped, never batch-shaped, so
        the scan, bucketed, and packed paths consume the key schedule
        identically (the parity anchor).
        """
        if self._rejit_pending:
            # draining toward a planned re-jit: no new admissions — they
            # would keep the wave alive (and prefill on a chip about to be
            # re-programmed)
            return
        admits = []
        for slot in range(self.max_batch):
            if not self.queue or not self.slot_free[slot]:
                continue
            admits.append((slot, self.queue.pop(0)))
        if not admits:
            return
        wave_key = self._next_key() if any(len(r.prompt) > 1
                                           for _, r in admits) else None
        # energy: every crossbar macro fires once per cached prompt
        # position (padding in the bucketed path excluded — documented
        # as useful-position accounting in repro.obs.energy)
        self.energy.add_processed(sum(max(len(r.prompt) - 1, 0)
                                      for _, r in admits))
        with self.obs.span("admit", n=len(admits)):
            if self.prefill_mode == "bucketed":
                self._admit_bucketed(admits, wave_key)
                return
            for slot, req in admits:
                with self.obs.span("prefill", slot=slot,
                                   length=int(len(req.prompt) - 1)):
                    mini_state = self.model.init_decode_state(
                        1, self.max_len)
                    mini_state = self._fill(mini_state, req.prompt,
                                            wave_key)
                self._bookkeep_admit(slot, req)
                with otrace.annotate(otrace.SERVE_SCATTER):
                    self._merge_slot(mini_state, slot)

    def _fill(self, state, prompt, wave_key):
        # Jitted scan over the prompt (minus the last token, which decodes
        # in the shared batch step).  One compile per distinct prompt
        # length; the bucketed path exists precisely to amortize that.
        if len(prompt) <= 1:
            return state
        tokens = jnp.asarray(np.asarray(prompt), jnp.int32)
        return self._jit_prefill(self.params, state, tokens, wave_key,
                                 length=len(prompt) - 1)

    def _bookkeep_admit(self, slot: int, req: Request):
        wait = self._step_ord - self._submit_ord.get(req.uid,
                                                     self._step_ord)
        self._m_queue_wait.record(wait)
        self._m_admitted.inc()
        self.obs.trace_event("admit", uid=req.uid, slot=slot,
                             queue_wait_steps=int(wait))
        self.slot_free[slot] = False
        self.slot_req[slot] = req
        # positions 0..len-2 are cached; the LAST prompt token decodes
        # in the shared batch step at position len-1.
        self.slot_pos[slot] = len(req.prompt) - 1
        self._slot_ntok[slot] = len(req.generated or [])
        self._set_slot_last(slot, int(req.prompt[-1]))

    def _set_slot_last(self, slot: int, tok: int):
        self.slot_last[slot] = tok
        if self._detok is not None:
            self._slot_last_dev = self._slot_last_dev.at[slot].set(tok)

    def _admit_bucketed(self, admits, wave_key):
        """Bucketed/packed admission: round the wave's longest prefill up
        to a compiled bucket, run the whole wave through that executable
        (packed: all rows in one call; unpacked: one row-call each), chunk
        with repeated largest-bucket calls when the prompt is longer than
        every bucket, then scatter the resulting cache rows into their
        batch slots."""
        groups = [admits] if self.pack_prefill else [[a] for a in admits]
        P = self._pack_rows
        for group in groups:
            lens = [len(req.prompt) - 1 for _, req in group]
            state = self._pack_template()
            l_max = max(lens)
            sp_buckets = []
            if l_max > 0:
                toks = np.zeros((P, l_max), np.int32)
                vlen = np.zeros((P,), np.int32)
                for row, (_, req) in enumerate(group):
                    toks[row, :lens[row]] = np.asarray(
                        req.prompt[:lens[row]], np.int32)
                    vlen[row] = lens[row]
                vlen_j = jnp.asarray(vlen)
                pos = 0
                with self.obs.span("prefill", rows=len(group),
                                   max_len=int(l_max)) as sp:
                    while pos < l_max:
                        bucket = self._bucket_for(l_max - pos)
                        ex = self._ensure_prefill_exec(bucket)
                        sp_buckets.append(bucket)
                        chunk = np.zeros((P, bucket), np.int32)
                        width = min(bucket, l_max - pos)
                        chunk[:, :width] = toks[:, pos:pos + width]
                        # the state's shared index carries the global
                        # position between chunks (cache writes and the
                        # fold_in key schedule both key off it)
                        state = ex(self.params, state, jnp.asarray(chunk),
                                   vlen_j, wave_key)
                        pos += bucket
                    sp.set(buckets=sp_buckets)
            for row, (slot, req) in enumerate(group):
                self._bookkeep_admit(slot, req)
            with otrace.annotate(otrace.SERVE_SCATTER):
                self._scatter_rows(state, [(row, slot) for row, (slot, _)
                                           in enumerate(group)])

    def _scatter_rows(self, mini, assign):
        """Scatter pack rows into their batch slots (generalizing the
        single-slot :meth:`_merge_slot` to a whole admission wave): per
        leaf, gather the assigned rows along the batch axis and commit
        them only at the assigned slots — exact copies, untouched slots
        keep their in-flight state bit-for-bit.  The shared index becomes
        the max over the assigned slots' positions, as in
        :meth:`_merge_slot`."""
        perm = np.zeros(self.max_batch, np.int64)
        mask = np.zeros(self.max_batch, bool)
        for row, slot in assign:
            perm[slot] = row
            mask[slot] = True
        perm_j = jnp.asarray(perm)
        mask_np = mask

        def sel(big, small, ax):
            if ax < 0:
                return big        # shared leaves (index) set below
            rows = jnp.take(small, perm_j, axis=ax)
            shape = [1] * big.ndim
            shape[ax] = self.max_batch
            return jnp.where(jnp.reshape(jnp.asarray(mask_np), shape),
                             rows, big)

        self.state = jax.tree.map(sel, self.state, mini,
                                  self._batch_axes())
        top = max((self.slot_pos[slot] for _, slot in assign), default=0)
        self.state["index"] = jnp.maximum(self.state["index"],
                                          jnp.asarray(np.int32(top)))

    def _merge_slot(self, mini_state, slot):
        """Copy the single-request cache into batch slot ``slot``."""

        def merge(big, small):
            if big.ndim == 0:
                return big
            # find the batch dim: mini has size 1 where big has max_batch
            for ax in range(big.ndim):
                if small.shape[ax] == 1 and big.shape[ax] == self.max_batch:
                    idx = [slice(None)] * big.ndim
                    idx[ax] = slice(slot, slot + 1)
                    return big.at[tuple(idx)].set(small)
            return big

        self.state = jax.tree.map(merge, self.state, mini_state)
        # global index = max over active slots; per-slot positions tracked
        # host-side (single shared index is exact when slots admit in waves;
        # documented simplification vs. per-slot index plumbing)
        self.state["index"] = jnp.maximum(
            self.state["index"], jnp.asarray(self.slot_pos[slot]))

    def step(self) -> Dict[int, int]:
        """One engine iteration: admit + decode. Returns {uid: token}.

        With ``detok_thread`` the returned batch is one that LANDED from
        an earlier step (at most one step of lag; {} while the first step
        is still in flight) — :meth:`detok_flush` joins the backlog.
        """
        with otrace.annotate_step(otrace.SERVE_STEP, self._step_ord):
            self.obs.set_step(self._step_ord)
            if self._rejit_pending and all(self.slot_free):
                # the wave drained: apply the deferred chip re-program,
                # then resume admission on the fresh traces
                self._rejit_pending = False
                self._on_chip_reprogram()
            if self._detok is not None:
                self._reap_detok_eos()
            self._admit()
            active = [s for s in range(self.max_batch)
                      if not self.slot_free[s]]
            if not active:
                self._step_ord += 1
                return self._drain_detok() if self._detok is not None \
                    else {}
            with self.obs.span("decode", active=len(active)):
                out = self._step_detok(active) if self._detok is not None \
                    else self._step_sync(active)
            self.energy.add_processed(len(active))
            if self.scheduler is not None and self.scheduler.tick():
                self._handle_reprogram_due(active)
            self._step_ord += 1
            return out

    def _step_sync(self, active) -> Dict[int, int]:
        """The synchronous decode step: dispatch, block on the host
        transfer, do the per-request bookkeeping inline."""
        with otrace.annotate(otrace.DECODE_INPUTS):
            tokens = jnp.asarray(self.slot_last[:, None], jnp.int32)
            positions = jnp.asarray(self.slot_pos, jnp.int32)
            key = self._next_key()
        with otrace.annotate(otrace.DECODE_DISPATCH):
            next_tok, self.state = self._jit_decode(
                self.params, self.state, tokens, positions, key)
        pairs = self.state.get("moe_pairs")
        with otrace.annotate(otrace.DECODE_SYNC):
            if pairs is None:
                next_np = np.asarray(next_tok)
            else:
                next_np, pairs = jax.device_get((next_tok, pairs))
        if pairs is not None:
            self._count_pairs(pairs, active)
        with otrace.annotate(otrace.DECODE_BOOKKEEP):
            out = {}
            for s in active:
                req = self.slot_req[s]
                tok = int(next_np[s])
                req.generated.append(tok)
                out[req.uid] = tok
                self.slot_last[s] = tok
                self.slot_pos[s] += 1
                self._note_token(s, req.uid)
                done = (len(req.generated) >= req.max_new_tokens
                        or tok == req.eos_id
                        or self.slot_pos[s] >= self.max_len - 1)
                if done:
                    self._note_finish(s, req.uid)
                    self.slot_free[s] = True
                    self.slot_req[s] = None
        return out

    def _step_detok(self, active) -> Dict[int, int]:
        """The pipelined decode step: dispatch against the device-side
        last-token vector (no host sync), hand the result to the detok
        worker, and return whatever batch already landed.

        The done-by-count check runs on host counters (the worker owns
        ``generated``); EOS detection necessarily lags one step — the
        slot keeps decoding one speculative token (discarded by the
        worker) and is reaped at the top of the next step.
        """
        with otrace.annotate(otrace.DECODE_INPUTS):
            tokens = self._slot_last_dev[:, None]
            positions = jnp.asarray(self.slot_pos, jnp.int32)
            key = self._next_key()
        with otrace.annotate(otrace.DECODE_DISPATCH):
            next_tok, self.state = self._jit_decode(
                self.params, self.state, tokens, positions, key)
        with otrace.annotate(otrace.DECODE_BOOKKEEP):
            mask = np.zeros(self.max_batch, bool)
            for s in active:
                mask[s] = True
            self._slot_last_dev = jnp.where(jnp.asarray(mask), next_tok,
                                            self._slot_last_dev)
            self._detok.put(next_tok,
                            [(s, self.slot_req[s]) for s in active],
                            self.state.get("moe_pairs"), self._count_pairs)
            for s in active:
                uid = self.slot_req[s].uid
                self.slot_pos[s] += 1
                self._note_token(s, uid)
                done = (self._slot_ntok[s]
                        >= self.slot_req[s].max_new_tokens
                        or self.slot_pos[s] >= self.max_len - 1)
                if done:
                    # the worker still holds its reference; streams
                    # finish landing asynchronously
                    self._note_finish(s, uid)
                    self.slot_free[s] = True
                    self.slot_req[s] = None
        return self._drain_detok()

    def _count_pairs(self, pairs, rows) -> None:
        """A MoE decode step's (token, expert) pairs over its real rows:
        ``moe.pairs_routed`` and ``moe.pairs_local`` (those routed to an
        expert held here), summed over layers."""
        routed, local = np.asarray(pairs)[rows].sum(axis=0)
        otrace.count(self.obs.metrics, **{
            otrace.MOE_PAIRS_ROUTED: int(routed),
            otrace.MOE_PAIRS_LOCAL: int(local)})

    def _note_token(self, s: int, uid: int) -> None:
        """Per-token obs bookkeeping at DISPATCH time (identical in the
        sync and detok paths — the ``_slot_ntok`` 0→1 transition marks the
        first token whoever owns ``generated``), so seeded traces and
        latency histograms are bitwise the same with or without the
        detokenize thread."""
        now = time.perf_counter()
        if self._slot_ntok[s] == 0:
            ttft = self._step_ord - self._submit_ord.pop(uid,
                                                         self._step_ord)
            self._m_ttft.record(ttft)
            sub_wall = self._submit_wall.pop(uid, None)
            if sub_wall is not None:
                self._m_ttft_ms.record((now - sub_wall) * 1e3)
            self.obs.trace_event("first_token", uid=uid,
                                 ttft_steps=int(ttft))
        else:
            self._m_itl.record(self._step_ord
                               - self._slot_last_tok_ord[s])
            self._m_itl_ms.record(
                (now - self._slot_last_tok_wall[s]) * 1e3)
        self._slot_ntok[s] += 1
        self._slot_last_tok_ord[s] = self._step_ord
        self._slot_last_tok_wall[s] = now
        self._m_tokens.inc()
        self.energy.add_generated(1)

    def _note_finish(self, s: int, uid: int) -> None:
        self._m_finished.inc()
        self.obs.trace_event("finish", uid=uid,
                             n_tokens=int(self._slot_ntok[s]))

    def _drain_detok(self) -> Dict[int, int]:
        """At most one landed step batch, so a caller counting tokens as
        ``len(step())`` per call stays exact across the pipeline lag."""
        return self._detok.pop_one()

    def _reap_detok_eos(self):
        """Free slots whose request hit EOS (worker-detected, one step
        after the synchronous path — the speculative extra token never
        lands in ``generated``)."""
        for slot, uid in self._detok.take_eos():
            req = self.slot_req[slot]
            if req is not None and req.uid == uid:
                self.slot_free[slot] = True
                self.slot_req[slot] = None

    def detok_flush(self) -> List[Dict[int, int]]:
        """Join the detokenize backlog (no-op without the thread): blocks
        until every handed-off step has landed, re-syncs the host
        last-token mirror, reaps any EOS that landed with the flush, and
        returns the landed step batches."""
        if self._detok is None:
            return []
        batches = self._detok.flush()
        self.slot_last = np.asarray(self._slot_last_dev, np.int32).copy()
        self._reap_detok_eos()
        return batches

    def shelf_tick(self, age_per_step_s: float) -> None:
        """Advance the device clock for a chip serving NO traffic this
        step (fleet shelf aging): an idle chip still sits powered in the
        rack, so retention drift accrues and the probe cadence keeps
        running — an unrouted canary can still fire its warning.  Same
        tick/reprogram machinery as :meth:`step`, age rate overridden."""
        if self.scheduler is None:
            return
        if self.scheduler.tick(age_per_step_s=age_per_step_s):
            self._handle_reprogram_due([])

    def _handle_reprogram_due(self, active):
        """A scheduler tick crossed the probe cadence and the chip wants
        re-programming; route it per the maintenance policy."""
        if self.external_maintenance:
            # fleet mode: the planner decides WHEN this chip drains.
            # Keep serving (and admitting) the old chip — physically
            # the re-program is deferred — until begin_drain().
            self._maint_pending = True
        elif self.drain_before_rejit \
                and not all(self.slot_free[s] for s in active):
            # planned re-jit: drain the in-flight wave first (the
            # deployed thresholds moved host-side, but the compiled
            # step keeps serving the old chip until the drain point)
            self._rejit_pending = True
        else:
            # also settles any earlier deferral — one reprogram covers
            # every threshold move up to the scheduler's current age
            self._rejit_pending = False
            self._on_chip_reprogram()

    def _on_chip_reprogram(self):
        """The scheduler moved the deployed thresholds (aging/recal).

        Weight crossbars drift on the same clock: re-realize them from the
        pristine params at the scheduler's current age (deterministic —
        the per-tile draws are TilePlan-keyed, so the same age is the same
        chip on every rebuild), then drop the stale jitted traces.

        A pending probe-driven *weight refresh* re-programs the crossbars
        instead of merely re-aging them: the generation salt draws a fresh
        per-tile write-noise population and the drift clock restarts at the
        re-program age.  When every stalled ramp is a col-tile bank whose
        activation maps to param leaves (``model.act_param_leaves``), only
        the crossbar col-tiles feeding those banks are rewritten (the
        per-tile refresh); otherwise the whole chip re-programs.
        """
        sched = self.scheduler
        if sched is None:
            # externally-forced drain on a schedulerless chip (fleet smoke):
            # nothing ages, so the selective re-jit keeps every warm
            # bucket and the compiled decode step
            self._maint_pending = False
            self._refresh_jit_selective()
            return
        # After a restored drain window the activations hold the OLD
        # (served) thresholds; push the scheduler's current-age state
        # before re-jitting.  In the immediate path this is a no-op (tick
        # already redeployed).
        sched.redeploy()
        if self.device is not None:
            stalled = list(sched.weight_refresh_ramps)
            if sched.consume_weight_refresh():
                self._refresh_ord += 1
                scope = self._per_tile_refresh_scope(stalled)
                if scope is not None:
                    for key in scope:
                        self._tile_gens[key] = {"gen": self._refresh_ord,
                                                "age_s": sched.age_s}
                else:
                    # full-chip rewrite supersedes any partials
                    self._weight_gen = self._refresh_ord
                    self._weight_prog_age_s = sched.age_s
                    self._tile_gens.clear()
        if self.device is not None \
                and (sched.policy.age_per_step_s > 0 or self._weight_gen
                     or self._tile_gens):
            t_eff = max(sched.age_s - self._weight_prog_age_s, 0.0)
            aged_dev = self.device.with_drift(t_eff)
            if aged_dev.has_build_stage:
                self.params = aged_dev.age_params(
                    self._pristine_params, generation=self._weight_gen,
                    leaf_overrides=self._tile_overrides_fn())
        self._maint_pending = False
        # bucket-aware: only executables whose traced thresholds moved are
        # dropped (a weight-only refresh keeps everything — params are
        # runtime arguments, not constants)
        self._refresh_jit_selective()

    def _per_tile_refresh_scope(self, stalled):
        """The bank keys eligible for a col-tile-scoped rewrite, or None.

        Per-tile needs every stalled ramp to be (a) a bank key — an
        unbanked ramp spans all of its activation's columns, so its refresh
        IS chip-wide for those leaves — and (b) an activation the model
        maps to param leaves.  Anything else falls back to the full
        re-program (correct, just coarser).
        """
        if not stalled:
            return None
        leaf_map = getattr(self.model, "act_param_leaves", None)
        if leaf_map is None:
            return None
        mapped = leaf_map()
        for key in stalled:
            if "@" not in key or key.split("@", 1)[0] not in mapped:
                return None
        return stalled

    def _tile_overrides_fn(self):
        """Realize ``_tile_gens`` as an ``age_params`` leaf_overrides
        callable: for each leaf feeding a refreshed bank, the TilePlan
        col-tiles intersecting that bank's output columns carry the bank's
        own (generation, drift-age) instead of the chip-wide ones."""
        if not self._tile_gens:
            return None
        from repro.core import crossbar as CB

        mapped = self.model.act_param_leaves()
        # act -> [(width, col_lo, col_hi, gen, prog_age)] in sorted key
        # order, so overlapping spans resolve deterministically
        spans: Dict[str, list] = {}
        for key, rec in sorted(self._tile_gens.items()):
            name, rest = key.split("@", 1)
            width_s, j_s = rest.split(":")
            width, j = int(width_s), int(j_s)
            bc = self._acts[name].cfg.bank_cols
            spans.setdefault(name, []).append(
                (width, j * bc, min((j + 1) * bc, width),
                 int(rec["gen"]), float(rec["age_s"])))
        sched_age = self.scheduler.age_s

        def overrides(path, shape):
            cov = {}
            for name, spanlist in spans.items():
                if not any(p in path for p in mapped.get(name, ())):
                    continue
                plan = CB.plan_tiles(shape[-2], shape[-1])
                for width, lo, hi, gen, prog_age in spanlist:
                    if shape[-1] != width:
                        continue
                    t_eff = max(sched_age - prog_age, 0.0)
                    for (ti, tj), _, cs in plan.blocks():
                        if ti == 0 and cs.start < hi and cs.stop > lo:
                            cov[tj] = (gen, t_eff)
            return cov or None

        return overrides

    def run_to_completion(self, max_iters: int = 10_000) -> int:
        """Drain the queue; returns the number of tokens generated."""
        n = 0
        for _ in range(max_iters):
            if not self.queue and all(self.slot_free):
                break
            n += len(self.step())
        # join the detokenize backlog (the loop's last steps are still
        # landing asynchronously) and count what it delivered
        n += sum(len(batch) for batch in self.detok_flush())
        if self._rejit_pending and all(self.slot_free):
            # settle a deferred chip re-program once the last wave drained,
            # so the deployment doesn't idle on stale traces
            self._rejit_pending = False
            self._on_chip_reprogram()
        return n

    def run_offline(self, requests=None, max_iters: int = 100_000) -> dict:
        """MLPerf-offline-style measured run: submit the whole burst up
        front, drain it, report wall-clock tokens/s plus the latency
        distributions (p50/p95/p99 TTFT and inter-token latency, in engine
        steps and in wall ms) and the costed energy efficiency
        (tokens-per-joule / TOPS/W under both periphery variants).  Call
        :meth:`warmup` first — compile time belongs outside the
        measurement."""
        for req in (requests or []):
            self.submit(req)
        t0 = time.perf_counter()
        n = self.run_to_completion(max_iters=max_iters)
        dt = time.perf_counter() - t0
        return {"tokens": int(n), "seconds": float(dt),
                "tokens_per_s": float(n / dt) if dt > 0 else 0.0,
                "ttft_steps": self._m_ttft.summary(),
                "itl_steps": self._m_itl.summary(),
                "ttft_ms": self._m_ttft_ms.summary(),
                "itl_ms": self._m_itl_ms.summary(),
                "energy": self.energy.report()}

    # -- checkpoint / restore (repro.ckpt) ------------------------------

    def _ckpt_tree(self, include_pristine: bool):
        """The array state of the deployment (structure must be stable
        between save and restore — see ``load_checkpoint``).

        ``pristine`` (the pre-aging params, needed to re-realize the
        crossbars at a future age) is only stored when a scheduler exists —
        without one nothing ever re-ages, and the copy would double the
        checkpoint for no reader.
        """
        tree = {
            "params": self.params,                       # aged, as served
            "state": self.state,
            "noise_key": self._noise_key,
            "slot_pos": np.asarray(self.slot_pos),
            "slot_last": np.asarray(self.slot_last),
            "slot_free": np.asarray(self.slot_free, np.bool_),
            # SERVED comparator thresholds per activation — the float64
            # arrays the compiled traces actually quantize with, so a
            # restore is bitwise the running chip even when the save lands
            # between scheduler probes or inside a drain window (where the
            # host-side thresholds have already moved ahead of the traces).
            "ramps": {name: np.asarray(thr)
                      for name, thr in self._served_ramps.items()},
            # The banked (n_col_tiles, P) layout per realized width — an
            # empty dict (no banked activations) contributes no leaves, so
            # schema-1 checkpoints load against this template unchanged.
            "ramp_banks": {
                name: {f"w{width}": np.asarray(thr)
                       for width, thr in sorted(banks.items())}
                for name, banks in self._served_bank_state().items()},
        }
        if include_pristine:
            tree["pristine"] = self._pristine_params
        return tree

    def save(self, root: str, step: int) -> str:
        """Atomic full-deployment checkpoint; returns the directory."""
        from repro.ckpt.checkpoint import save_checkpoint

        # land the detokenize backlog first: `generated` streams and the
        # host last-token mirror must be caught up with the device before
        # they are written down
        self.detok_flush()
        meta = {
            "schema": self.SCHEMA,
            "engine": {"max_batch": self.max_batch, "max_len": self.max_len},
            "device": None if self.device is None else self.device.to_dict(),
            "scheduler": None if self.scheduler is None
            else self.scheduler.to_dict(),
            # bank inventory: restore realizes these widths BEFORE building
            # the template tree, so the leaf paths line up
            "banks": {name: sorted(act.banks())
                      for name, act in self._acts.items() if act.banks()},
            "lifecycle": {"weight_gen": self._weight_gen,
                          "weight_prog_age_s": self._weight_prog_age_s,
                          "rejit_pending": self._rejit_pending,
                          "maint_pending": self._maint_pending,
                          "refresh_ord": self._refresh_ord,
                          "tile_gens": {k: dict(v) for k, v
                                        in self._tile_gens.items()}},
            "requests": {
                "slots": [None if r is None else r.to_dict()
                          for r in self.slot_req],
                "queue": [r.to_dict() for r in self.queue],
            },
            # Observability rides along: metrics snapshot + the tracer's
            # step/seq clock + the per-request/per-slot step bookkeeping,
            # so a restored deployment's counters, latency histograms, and
            # JSONL trace continue exactly where the saved run stopped
            # (the trace-determinism-across-resume contract).
            "obs": {
                **self.obs.snapshot(),
                "step_ord": int(self._step_ord),
                "submit_ord": {str(k): int(v)
                               for k, v in self._submit_ord.items()},
                "slot_last_tok_ord": [int(x)
                                      for x in self._slot_last_tok_ord],
            },
        }
        return save_checkpoint(
            root, step,
            self._ckpt_tree(include_pristine=self.scheduler is not None),
            metadata=meta)

    @classmethod
    def restore(cls, model, root: str, *, step: Optional[int] = None,
                params_like=None,
                drain_before_rejit: bool = False,
                external_maintenance: bool = False,
                prefill: str = "scan",
                prefill_buckets=None,
                pack_prefill: bool = False,
                detok_thread: bool = False,
                obs=None) -> "ServingEngine":
        """Resume a checkpointed deployment: same chip, same next token.

        ``params_like``: a pytree matching the model's params structure
        (shapes/dtypes only — values are overwritten).  Defaults to
        ``serving_params(model, PRNGKey(0))``.  The restored engine reproduces the
        uninterrupted run bit-for-bit: aged params, programmed thresholds,
        scheduler clock, per-step noise keys (the checkpointed key
        schedule, not a fresh seed — bitwise resume IS the contract),
        decode caches, and in-flight requests all come from the checkpoint.
        """
        from repro.ckpt.checkpoint import load_checkpoint, read_metadata
        from repro.core.device import device_from_dict
        from repro.serve.lifecycle import RecalScheduler

        step, meta = read_metadata(root, step=step)
        if "engine" not in meta:
            hint = ("this is a fleet manifest — restore via "
                    "repro.serve.fleet.FleetEngine.restore"
                    if isinstance(meta, dict) and "fleet" in meta else
                    "train checkpoints restore via repro.ckpt directly")
            raise ValueError(
                f"checkpoint at {root!r} (step {step}) is not a "
                f"ServingEngine deployment checkpoint (no 'engine' "
                f"metadata); {hint}")
        schema = int(meta.get("schema", 1))
        if schema > cls.SCHEMA:
            raise ValueError(
                f"deployment checkpoint schema {schema} is newer than this "
                f"build understands (<= {cls.SCHEMA}); upgrade repro, or "
                "re-serve and re-checkpoint with this version")
        if schema < 2:
            # schema 1 (PR 4 era): no threshold banks, no lifecycle
            # bookkeeping — migrate by filling the v2 fields with their
            # pre-bank semantics (empty bank inventory, generation 0).
            meta.setdefault("banks", {})
            meta.setdefault("lifecycle", {})
        if params_like is None:
            params_like = serving_params(model, jax.random.PRNGKey(0))
        eng = cls(model, params_like,
                  max_batch=meta["engine"]["max_batch"],
                  max_len=meta["engine"]["max_len"],
                  drain_before_rejit=drain_before_rejit,
                  external_maintenance=external_maintenance,
                  prefill=prefill, prefill_buckets=prefill_buckets,
                  pack_prefill=pack_prefill, detok_thread=detok_thread,
                  obs=obs)
        # Realize the checkpointed bank inventory BEFORE building the
        # restore template, so the leaf paths line up with the save — and
        # fail with a clear bank_cols hint in BOTH mismatch directions
        # (instead of a tree-mismatch error deep in repro.ckpt).
        for name, widths in meta["banks"].items():
            act = eng._acts.get(name)
            if act is None:
                raise ValueError(
                    f"checkpoint carries threshold banks for activation "
                    f"{name!r} but the model has no such NL-ADC "
                    f"activation; have {sorted(eng._acts)}")
            for width in widths:
                if act.bank_for(int(width)) is None:
                    raise ValueError(
                        f"checkpoint carries a threshold bank for {name!r} "
                        f"at width {width} but this model config does not "
                        f"bank that width (bank_cols={act.cfg.bank_cols}); "
                        "restore with the bank_cols the deployment was "
                        "serving with (--bank-cols)")
        for name, act in eng._acts.items():
            saved = {int(w) for w in meta["banks"].get(name, [])}
            extra = sorted(set(act.banks()) - saved)
            if extra:
                raise ValueError(
                    f"model config banks thresholds for {name!r} at widths "
                    f"{extra} but the checkpoint has none there (saved "
                    f"with a different bank_cols"
                    f"{' — or a pre-bank schema-1 deployment' if schema < 2 else ''}); "
                    "re-serve a fresh deployment or restore with the "
                    "original bank_cols")
        has_sched = meta["scheduler"] is not None
        tree, _, _ = load_checkpoint(
            root, eng._ckpt_tree(include_pristine=has_sched), step=step)
        # load_checkpoint returns host numpy; the decode state is mutated
        # with jnp .at[] updates (slot merge) so put it back on device.
        eng.params = jax.tree.map(jnp.asarray, tree["params"])
        # without a scheduler nothing re-ages, so the served params stand
        # in for pristine (never read again)
        eng._pristine_params = jax.tree.map(
            jnp.asarray, tree["pristine"] if has_sched else tree["params"])
        eng.state = jax.tree.map(jnp.asarray, tree["state"])
        eng._noise_key = jnp.asarray(tree["noise_key"])
        eng.slot_pos = np.asarray(tree["slot_pos"], np.int32)
        eng.slot_last = np.asarray(tree["slot_last"], np.int32)
        eng.slot_free = [bool(b) for b in np.asarray(tree["slot_free"])]
        eng.slot_req = [None if d is None else Request.from_dict(d)
                        for d in meta["requests"]["slots"]]
        eng.queue = [Request.from_dict(d) for d in meta["requests"]["queue"]]
        # throughput-path mirrors: the checkpoint was flushed at save, so
        # the host arrays are authoritative (any prefill/detok mode can
        # resume any checkpoint — the modes share one state layout)
        if eng._detok is not None:
            eng._slot_last_dev = jnp.asarray(eng.slot_last, jnp.int32)
        for s, req in enumerate(eng.slot_req):
            eng._slot_ntok[s] = 0 if req is None else len(req.generated)
        if meta["device"] is not None:
            eng.device = device_from_dict(meta["device"])
        # Reprogram the chip exactly as checkpointed.
        for name, thr in tree["ramps"].items():
            act = eng._acts[name]
            act.redeploy(act.ramp.with_thresholds(
                np.asarray(thr, np.float64)))
        for name, banks in tree.get("ramp_banks", {}).items():
            act = eng._acts[name]
            for wkey, thr in banks.items():
                width = int(wkey[1:])                   # "w{width}"
                ideal = act.bank_for(width).ideal
                act.redeploy_bank(width, [
                    ideal.with_thresholds(np.asarray(row, np.float64))
                    for row in np.asarray(thr)])
        lc = meta["lifecycle"]
        eng._weight_gen = int(lc.get("weight_gen", 0))
        eng._weight_prog_age_s = float(lc.get("weight_prog_age_s", 0.0))
        eng._rejit_pending = bool(lc.get("rejit_pending", False))
        eng._maint_pending = bool(lc.get("maint_pending", False))
        eng._refresh_ord = int(lc.get("refresh_ord", lc.get("weight_gen",
                                                            0)))
        eng._tile_gens = {k: {"gen": int(v["gen"]),
                              "age_s": float(v["age_s"])}
                          for k, v in lc.get("tile_gens", {}).items()}
        if meta["scheduler"] is not None:
            eng.scheduler = RecalScheduler.from_dict(
                meta["scheduler"], eng._acts)
            eng.scheduler.obs = eng.obs
        # Observability: restore counters/histograms and the trace clock so
        # the resumed deployment's JSONL trace and latency stats continue
        # bit-for-bit (absent in pre-obs checkpoints — fresh clock then).
        obs_meta = meta.get("obs")
        if obs_meta:
            eng.obs.restore(obs_meta)
            eng._step_ord = int(obs_meta.get("step_ord", 0))
            eng._submit_ord = {int(k): int(v) for k, v
                               in obs_meta.get("submit_ord", {}).items()}
            slto = obs_meta.get("slot_last_tok_ord")
            if slto is not None and len(slto) == eng.max_batch:
                eng._slot_last_tok_ord = np.asarray(slto, np.int64)
        # wall anchors are process-local: restart them at restore time so
        # the (non-deterministic, strip_wall-excluded) ms histograms never
        # see a cross-process epoch delta
        eng._slot_last_tok_wall[:] = time.perf_counter()
        eng._refresh_jit()
        return eng
