"""Elastic scaling plan: restore a run onto a different device count.

Checkpoints store full logical arrays keyed by tree path
(:mod:`repro.ckpt.checkpoint`), so the only mesh-dependent objects are the
shardings.  ``replan`` computes the new mesh + shardings for the surviving
device set and the data-pipeline reshard (global batch is preserved; the
per-host slice changes).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.dist import sharding as SH


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    old_shape: Dict[str, int]
    new_shape: Dict[str, int]
    global_batch: int

    @property
    def new_data_degree(self) -> int:
        return int(np.prod([v for k, v in self.new_shape.items()
                            if k in ("pod", "data")]))

    def local_batch(self, n_hosts: int) -> int:
        assert self.global_batch % n_hosts == 0
        return self.global_batch // n_hosts


def plan_for_devices(n_devices: int, *, global_batch: int,
                     model_parallel: int = 16,
                     old_mesh: Optional[Mesh] = None) -> ElasticPlan:
    """Largest (data, model) mesh that fits the surviving device count.

    Keeps the model axis fixed (param layout unchanged within replicas) and
    shrinks/grows the data axis — the standard elastic move: losing a host
    costs one data replica, never a TP shard.
    """
    model = model_parallel
    while model > 1 and n_devices % model:
        model //= 2
    data = n_devices // model
    # data axis must divide the global batch
    while data > 1 and global_batch % data:
        data -= 1
    new_shape = {"data": data, "model": model}
    old_shape = dict(old_mesh.shape) if old_mesh is not None else {}
    return ElasticPlan(old_shape=old_shape, new_shape=new_shape,
                       global_batch=global_batch)


def build_mesh(plan: ElasticPlan) -> Mesh:
    n = int(np.prod(list(plan.new_shape.values())))
    devices = np.asarray(jax.devices()[:n]).reshape(
        tuple(plan.new_shape.values()))
    return Mesh(devices, tuple(plan.new_shape.keys()))


def reshard(tree, mesh: Mesh, *, replicate_all: bool = False):
    """device_put a host tree onto a (new) mesh with the standard rules."""
    specs = SH.param_specs(tree, mesh, replicate_all=replicate_all)
    shardings = SH.shardings_for(specs, mesh)
    return jax.tree.map(jax.device_put, tree, shardings)


def init_sharded(init, key, mesh: Mesh, *, replicate_all: bool = False):
    """``reshard(init(key), mesh)`` without the whole tree on one device.

    ``init`` runs under ``jit`` with the standard layout as its output
    sharding, so each device makes only its own shards; run eagerly (or
    jitted without shardings) it would build every parameter on the first
    device before the copy out.  The values are the same either way.
    """
    shapes = jax.eval_shape(init, key)
    specs = SH.param_specs(shapes, mesh, replicate_all=replicate_all)
    return jax.jit(init, out_shardings=SH.shardings_for(specs, mesh))(key)


def plan_request_rebalance(displaced, loads: Dict[str, int]
                           ) -> Dict[str, list]:
    """Assign displaced serving requests to surviving chips, least-loaded
    first.

    The serving-side elastic move: a chip pulled for re-program
    (:meth:`repro.serve.engine.ServingEngine.take_queue`) hands its queued
    requests to siblings.  ``loads`` maps chip id -> current load (active +
    queued); each request goes to the momentarily least-loaded chip, ties
    broken by chip id — fully deterministic, so a fleet checkpoint replays
    the identical assignment.  Returns chip id -> list of requests (every
    id present, possibly empty).
    """
    if not loads:
        raise ValueError("no surviving chips to rebalance onto")
    cur = dict(loads)
    out: Dict[str, list] = {cid: [] for cid in loads}
    for req in displaced:
        cid = min(sorted(cur), key=lambda c: cur[c])
        out[cid].append(req)
        cur[cid] += 1
    return out
