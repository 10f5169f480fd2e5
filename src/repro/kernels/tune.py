"""Per-shape kernel autotuning: block-size cache + trace-time resolution.

Every Pallas kernel in :mod:`repro.kernels` tiles its operands with block
sizes that were, until this module, hard-coded module constants
(``DEFAULT_BLOCKS``).  On real hardware the right blocks depend on the
kernel x shape x dtype x platform — the same discipline the 65 nm NL-CIM
macro applies to its peripheral throughput per array.  This module is the
seam that makes the choice data-driven without touching kernel code:

* ``TuneCache`` — a JSON cache of best-per-shape blocks, keyed exactly like
  the BENCH files: ``kernel|shape|dtype|platform|backend_mode``.  Entries
  record the selected blocks, how they were selected (``measured`` wall
  time where the platform can compile Pallas, the deterministic ``proxy``
  cost model in interpret mode), and any clamping that was applied.
* ``resolve_blocks`` — consulted at trace time by ``repro.kernels.ops``
  (i.e. by every ``core.backend`` pallas dispatch).  Precedence:

      1. explicit per-kernel override — ``--kernel-blocks`` CLI /
         ``set_block_overrides`` / ``REPRO_KERNEL_BLOCKS`` env;
      2. the active cache — ``set_active_cache`` / ``--kernel-cache`` CLI /
         ``REPRO_KERNEL_CACHE`` env (path to a cache JSON);
      3. the caller's shape rule, where it passes one (``fallback``),
         else the kernel's ``DEFAULT_BLOCKS``.  ``ops.fused_matmul_nladc``
         passes ``fused_matmul_nladc.plan_blocks``: ``DEFAULT_BLOCKS`` at
         256 rows or more, a skinny plan below (rows fitted to the sublane
         tile, the whole of K in one step).  A miss is bitwise the
         pre-autotune behaviour everywhere except there: a skinny call's
         new bk groups the f32 sums differently.

* ``autotune`` — the sweep harness.  Where Pallas compiles (on a TPU)
  each candidate is timed and the fastest wins; in interpret mode (CI)
  candidates are ranked by a deterministic static cost model (padding
  waste x grid overhead x VMEM fit) so the sweep is exercisable
  everywhere and the cache file it writes is byte-deterministic.  The
  jnp-ref wall time is measured once per shape as the recorded
  throughput proxy (it goes to ``BENCH_kernels.json``, not into the
  selection).

Clamp accounting: kernel wrappers call :func:`warn_clamp` instead of
silently shrinking a requested block to the operand — a one-time
``KernelBlockClampWarning`` names the kernel/shape, and the clamped value
is recorded on the live cache entry (see ``benchmarks/kernel_tune.py``).
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Candidate tile extents per blocked dimension (MXU/VPU aligned).  bk also
# sweeps 1024: deep-K shapes amortize the revisiting pattern further.
_CAND_MN = (128, 256, 512)
_CAND_K = (128, 256, 512, 1024)
# VMEM working-set budget per grid step (bytes); candidates past it are
# heavily penalized by the proxy model (they cannot double-buffer).
VMEM_BUDGET = 12 * 1024 * 1024


class KernelBlockClampWarning(UserWarning):
    """A requested kernel block was clamped to the operand shape."""


# ---------------------------------------------------------------------------
# Kernel registry (lazy: kernel modules import this module for warn_clamp)
# ---------------------------------------------------------------------------

def default_blocks(kernel: str) -> Tuple[int, ...]:
    """The kernel module's hard-coded default — the cache-miss fallback."""
    # importlib, not `from repro.kernels import ...`: the package __init__
    # re-exports same-named wrapper *functions* that shadow the submodule
    # attributes once the package is fully initialized
    import importlib

    mod = importlib.import_module
    table = {
        "fused_matmul_nladc": tuple(
            mod("repro.kernels.fused_matmul_nladc").DEFAULT_BLOCKS),
        "analog_tile": tuple(
            mod("repro.kernels.crossbar_mac").DEFAULT_BLOCKS),
        "nladc": tuple(mod("repro.kernels.nladc_kernel").DEFAULT_BLOCK),
        "lstm_gates": tuple(mod("repro.kernels.lstm_cell").DEFAULT_BLOCK),
    }
    try:
        return table[kernel]
    except KeyError:
        raise KeyError(f"unknown tunable kernel {kernel!r}; "
                       f"known: {sorted(table)}") from None


# (kernel) -> how its block tuple maps onto its shape tuple: blocks[i]
# tiles shape[dim_of_block[i]].  fused matmul: blocks (bm, bn, bk) over
# shape (m, k, n); elementwise kernels: (bm, bn) over (m, n).
_BLOCK_DIMS = {
    "fused_matmul_nladc": (0, 2, 1),
    "analog_tile": (0, 2, 1),
    "nladc": (0, 1),
    "lstm_gates": (0, 1),
}


def tunable_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_BLOCK_DIMS))


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def platform() -> str:
    return jax.default_backend()


def backend_mode() -> str:
    """"interpret" or "compiled" — mirrors ``repro.kernels.interpret_mode``."""
    from repro.kernels import ops

    return "interpret" if ops.interpret_mode() else "compiled"


def cache_key(kernel: str, shape: Sequence[int], dtype=jnp.float32,
              plat: Optional[str] = None, mode: Optional[str] = None) -> str:
    shape_s = "x".join(str(int(d)) for d in shape)
    return "|".join([kernel, shape_s, jnp.dtype(dtype).name,
                     plat or platform(), mode or backend_mode()])


class TuneCache:
    """Best-per-shape kernel blocks, JSON-serializable.

    ``entries`` maps :func:`cache_key` strings to dicts with at least
    ``{"blocks": [...]}`` plus selection metadata (``source``, ``score`` /
    ``us``, ``clamped``).
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 meta: Optional[dict] = None):
        self.entries: Dict[str, dict] = dict(entries or {})
        self.meta = dict(meta or {})

    # -- serialization -------------------------------------------------

    def to_dict(self) -> dict:
        return {"version": 1, "meta": self.meta,
                "entries": {k: self.entries[k]
                            for k in sorted(self.entries)}}

    @classmethod
    def from_dict(cls, d: dict) -> "TuneCache":
        if isinstance(d, dict) and "entries" not in d and \
                isinstance(d.get("tune"), dict):
            d = d["tune"]   # accept a benchmarks/BENCH_kernels.json wrapper
        if not isinstance(d, dict) or "entries" not in d:
            raise ValueError("not a kernel tune cache (no 'entries' key)")
        if d.get("version", 1) != 1:
            raise ValueError(f"unsupported tune-cache version "
                             f"{d.get('version')!r}")
        return cls(entries=d["entries"], meta=d.get("meta", {}))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "TuneCache":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    # -- access --------------------------------------------------------

    def lookup(self, kernel: str, shape: Sequence[int],
               dtype=jnp.float32) -> Optional[Tuple[int, ...]]:
        e = self.entries.get(cache_key(kernel, shape, dtype))
        if e is None:
            return None
        return tuple(int(b) for b in e["blocks"])

    def record(self, kernel: str, shape: Sequence[int], dtype,
               blocks: Sequence[int], **extra) -> dict:
        e = {"kernel": kernel, "shape": [int(d) for d in shape],
             "blocks": [int(b) for b in blocks]}
        e.update(extra)
        self.entries[cache_key(kernel, shape, dtype)] = e
        return e

    def note_clamp(self, kernel: str, shape: Sequence[int], dtype,
                   requested: Sequence[int],
                   clamped: Sequence[int]) -> None:
        """Annotate (creating if needed) the entry for a clamped call."""
        key = cache_key(kernel, shape, dtype)
        e = self.entries.setdefault(
            key, {"kernel": kernel, "shape": [int(d) for d in shape],
                  "blocks": [int(b) for b in clamped], "source": "clamp"})
        e["clamped"] = {"requested": [int(b) for b in requested],
                        "applied": [int(b) for b in clamped]}


# ---------------------------------------------------------------------------
# Active cache + overrides (module state consulted at trace time)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[TuneCache] = None
_ACTIVE_FROM_ENV: Tuple[str, Optional[TuneCache]] = ("", None)
_OVERRIDES: Dict[str, Tuple[int, ...]] = {}
_ENV_OVERRIDES: Tuple[str, Dict[str, Tuple[int, ...]]] = ("", {})
_WARNED: set = set()


def set_active_cache(cache: Optional[TuneCache]) -> None:
    """Install (or clear with ``None``) the process-wide tune cache."""
    global _ACTIVE
    _ACTIVE = cache


def active_cache() -> Optional[TuneCache]:
    """The explicit cache, else the ``REPRO_KERNEL_CACHE`` env cache."""
    global _ACTIVE_FROM_ENV
    if _ACTIVE is not None:
        return _ACTIVE
    path = os.environ.get("REPRO_KERNEL_CACHE", "")
    if not path:
        return None
    if _ACTIVE_FROM_ENV[0] != path:
        _ACTIVE_FROM_ENV = (path, TuneCache.load(path))
    return _ACTIVE_FROM_ENV[1]


def parse_block_spec(spec: str) -> Dict[str, Tuple[int, ...]]:
    """``"fused_matmul_nladc=128x128x512,nladc=256x512"`` -> overrides.

    Block extents are separated by ``x`` (``128x128x512``); kernels by
    commas.  Each kernel's extent count must match its block rank.
    """
    out: Dict[str, Tuple[int, ...]] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if "=" not in part:
            raise ValueError(f"--kernel-blocks entry {part!r} is not "
                             f"KERNEL=BMxBNxBK form")
        kernel, _, vals = part.partition("=")
        kernel = kernel.strip()
        if kernel not in _BLOCK_DIMS:
            raise ValueError(f"unknown tunable kernel {kernel!r}; "
                             f"known: {sorted(_BLOCK_DIMS)}")
        blocks = tuple(int(v) for v in vals.strip().split("x"))
        want = len(_BLOCK_DIMS[kernel])
        if len(blocks) != want or any(b <= 0 for b in blocks):
            raise ValueError(
                f"{kernel} takes {want} positive block extents, got {vals!r}")
        out[kernel] = blocks
    return out


def set_block_overrides(spec: str) -> None:
    """Install per-kernel forced blocks (the ``--kernel-blocks`` CLI)."""
    _OVERRIDES.clear()
    _OVERRIDES.update(parse_block_spec(spec))


def clear_block_overrides() -> None:
    _OVERRIDES.clear()


def _env_overrides() -> Dict[str, Tuple[int, ...]]:
    global _ENV_OVERRIDES
    spec = os.environ.get("REPRO_KERNEL_BLOCKS", "")
    if _ENV_OVERRIDES[0] != spec:
        _ENV_OVERRIDES = (spec, parse_block_spec(spec) if spec else {})
    return _ENV_OVERRIDES[1]


def configure(blocks_spec: str = "", cache_path: str = "") -> None:
    """One-call CLI hookup (``--kernel-blocks`` / ``--kernel-cache``)."""
    if blocks_spec:
        set_block_overrides(blocks_spec)
    if cache_path:
        set_active_cache(TuneCache.load(cache_path))


def resolve_blocks(kernel: str, shape: Sequence[int], dtype=jnp.float32,
                   fallback: Optional[Sequence[int]] = None
                   ) -> Tuple[int, ...]:
    """The trace-time block choice for one kernel call.

    Explicit override > active-cache hit > ``fallback`` > the kernel
    module's historical ``DEFAULT_BLOCKS``.  Without a ``fallback`` a cache
    miss is bitwise the pre-autotune behaviour; the fused matmul's
    fallback differs from it below 256 rows only (module docstring).
    """
    ov = _OVERRIDES.get(kernel) or _env_overrides().get(kernel)
    if ov is not None:
        return ov
    cache = active_cache()
    if cache is not None:
        hit = cache.lookup(kernel, shape, dtype)
        if hit is not None:
            return hit
    if fallback is not None:
        return tuple(fallback)
    return default_blocks(kernel)


def warn_clamp(kernel: str, shape: Sequence[int], requested: Sequence[int],
               clamped: Sequence[int], dtype=jnp.float32) -> None:
    """One-time warning (per kernel x shape x request) on block clamping.

    Also records the clamped value on the live cache entry so a
    re-recorded cache ships the actually-used blocks, not the fiction.
    """
    key = (kernel, tuple(int(d) for d in shape),
           tuple(int(b) for b in requested))
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(
            f"{kernel}: requested blocks {tuple(requested)} clamped to "
            f"{tuple(clamped)} for operand shape {tuple(shape)} — tune "
            f"this shape (benchmarks/kernel_tune.py) or pass aligned "
            f"blocks", KernelBlockClampWarning, stacklevel=3)
    cache = active_cache()
    if cache is not None:
        cache.note_clamp(kernel, shape, dtype, requested, clamped)


def _reset_for_tests() -> None:
    """Clear all module state (tests only)."""
    global _ACTIVE, _ACTIVE_FROM_ENV, _ENV_OVERRIDES
    _ACTIVE = None
    _ACTIVE_FROM_ENV = ("", None)
    _ENV_OVERRIDES = ("", {})
    _OVERRIDES.clear()
    _WARNED.clear()


# ---------------------------------------------------------------------------
# Autotune harness
# ---------------------------------------------------------------------------

def _aligned_candidates(kernel: str, shape: Sequence[int]) -> List[Tuple]:
    """The candidate block grid for one shape, clamp-annotated.

    Each candidate is ``(blocks, clamped_from)`` where ``clamped_from`` is
    the pre-clamp proposal when the operand was smaller than the tile
    (recorded in the cache entry), else ``None``.
    """
    dims = _BLOCK_DIMS[kernel]
    per_axis: List[List[Tuple[int, Optional[int]]]] = []
    for i, d in enumerate(dims):
        cand = _CAND_K if (kernel in ("fused_matmul_nladc", "analog_tile")
                           and i == 2) else _CAND_MN
        size = int(shape[d])
        vals: List[Tuple[int, Optional[int]]] = []
        for c in cand:
            if c <= size:
                vals.append((c, None))
            else:
                vals.append((size, c))   # clamped to the operand
        # dedupe preserving the smallest pre-clamp proposal
        seen: Dict[int, Optional[int]] = {}
        for v, req in vals:
            if v not in seen or (req is not None and seen[v] is None):
                seen[v] = seen.get(v) if v in seen and seen[v] is None \
                    else req
        per_axis.append(sorted(seen.items()))
    out: List[Tuple] = []

    def rec(i, blocks, reqs):
        if i == len(per_axis):
            clamped = tuple(r if r is not None else b
                            for b, r in zip(blocks, reqs))
            out.append((tuple(blocks),
                        clamped if any(r is not None for r in reqs)
                        else None))
            return
        for v, req in per_axis[i]:
            rec(i + 1, blocks + [v], reqs + [req])

    rec(0, [], [])
    return out


def proxy_score(kernel: str, shape: Sequence[int],
                blocks: Sequence[int]) -> float:
    """Deterministic static cost used when wall time cannot be measured.

    padded-work x grid-overhead x VMEM-fit — not a performance claim, just
    a total order that prefers aligned, budget-fitting tiles with minimal
    padding waste.  Re-record with measured timings on real hardware.
    """
    dims = _BLOCK_DIMS[kernel]
    padded = 1.0
    grid = 1.0
    for b, d in zip(blocks, dims):
        size = int(shape[d])
        steps = -(-size // b)
        padded *= steps * b
        grid *= steps
    if kernel in ("fused_matmul_nladc", "analog_tile"):
        bm, bn, bk = blocks
        vmem = 4 * (bm * bk + bk * bn + 2 * bm * bn)
    else:
        bm, bn = blocks
        vmem = 4 * 2 * bm * bn
    fit = 1.0 if vmem <= VMEM_BUDGET else 8.0
    return padded * (1.0 + 0.002 * grid) * fit


def _measure_us(fn, *args, n: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def _kernel_call(kernel: str, shape, dtype, blocks, seed: int = 0):
    """(fn, args) running the Pallas kernel at ``blocks`` on seeded data."""
    import functools

    from repro.core.nladc import build_ramp
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    ramp = build_ramp("swish", 5)
    if kernel in ("fused_matmul_nladc", "analog_tile"):
        m, k, n = shape
        x = jnp.asarray(rng.normal(0, 0.4, (m, k)).astype(np.float32), dtype)
        w = jnp.asarray(rng.normal(0, 0.2, (k, n)).astype(np.float32), dtype)
        if kernel == "fused_matmul_nladc":
            fn = functools.partial(ops.fused_matmul_nladc, ramp=ramp,
                                   blocks=blocks)
            return jax.jit(lambda a, b: fn(a, b)), (x, w)
        fn = functools.partial(ops.analog_tile, ramp=ramp, blocks=blocks)
        return jax.jit(lambda a, b: fn(a, b)), (x, w)
    if kernel == "nladc":
        m, n = shape
        x = jnp.asarray(rng.normal(0, 2, (m, n)).astype(np.float32), dtype)
        return jax.jit(lambda a: ops.nladc(a, ramp, block=blocks)), (x,)
    if kernel == "lstm_gates":
        b, h = shape
        sig, tnh = build_ramp("sigmoid", 5), build_ramp("tanh", 5)
        g = jnp.asarray(rng.normal(0, 1.5, (b, 4 * h)).astype(np.float32))
        c = jnp.asarray(rng.normal(0, 0.5, (b, h)).astype(np.float32))
        return jax.jit(lambda a, b2: ops.lstm_gates(a, b2, sig, tnh,
                                                    block=blocks)), (g, c)
    raise KeyError(kernel)


def autotune_kernel(kernel: str, shape: Sequence[int], dtype=jnp.float32,
                    *, cache: TuneCache, measure: Optional[str] = None,
                    n: int = 3) -> dict:
    """Sweep candidates for one kernel x shape and record the winner.

    ``measure``: ``"wall"`` times each candidate's compiled Pallas call
    (requires a TPU, where the kernels compile); ``"proxy"`` ranks by
    :func:`proxy_score` (deterministic, the interpret-mode/CI default).
    ``None`` auto-selects.
    """
    from repro.kernels import ops

    if measure is None:
        measure = "proxy" if ops.interpret_mode() else "wall"
    cands = _aligned_candidates(kernel, shape)
    best = None
    for blocks, clamped_from in sorted(cands):
        if measure == "wall":
            fn, args = _kernel_call(kernel, shape, dtype, blocks)
            cost = _measure_us(fn, *args, n=n)
        else:
            cost = proxy_score(kernel, shape, blocks)
        if best is None or (cost, blocks) < (best[0], best[1]):
            best = (cost, blocks, clamped_from)
    cost, blocks, clamped_from = best
    extra = {"source": "measured" if measure == "wall" else "proxy"}
    if measure == "wall":
        extra["us"] = round(cost, 2)
    else:
        extra["score"] = cost
    entry = cache.record(kernel, shape, dtype, blocks, **extra)
    if clamped_from is not None:
        cache.note_clamp(kernel, shape, dtype, clamped_from, blocks)
    return entry


def autotune(shapes: Dict[str, Iterable[Sequence[int]]], dtype=jnp.float32,
             *, cache: Optional[TuneCache] = None,
             measure: Optional[str] = None) -> TuneCache:
    """Sweep ``{kernel: [shape, ...]}`` into a (new or given) cache."""
    cache = cache if cache is not None else TuneCache(
        meta={"platform": platform(), "backend_mode": backend_mode()})
    for kernel, shape_list in sorted(shapes.items()):
        for shape in shape_list:
            autotune_kernel(kernel, tuple(shape), dtype, cache=cache,
                            measure=measure)
    return cache
