#!/usr/bin/env python3
"""Compile a cell's engine programs and its reference layer for a
described TPU v5e chip, without the chip, and print each program's
``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py qwen2.5-3b.decode-heavy

Compiled: the decode step (``ServingEngine._decode_all``), one bucket
executable per prefill bucket (``_prefill_packed``) and one layer of the
plain reference at the check's size, all with the Pallas kernels lowered
for the TPU (not interpreted).  Nothing runs; shapes only.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run as R
    from repro.kernels import ops
    from repro.nn.model import build
    from repro.serve.engine import ServingEngine

    jax.config.update("jax_enable_compilation_cache", False)
    ops.interpret_mode = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    for name in argv:
        cell = R.load_json("workloads", name + ".json")
        config = R.load_json("configs", cell["config"] + ".json")
        model = build(R.program_config(config))
        refmod = R.load_module("reference", config["reference"])
        ref_cfg = R.reference_config(config)
        dt = jnp.dtype(model.cfg.serve_params_dtype)
        params = shapes(jax.eval_shape(
            lambda k: refmod.init_params(ref_cfg, k, dt),
            jax.random.PRNGKey(0)))
        eng = dict(cell["engine"])
        b, length = eng["max_batch"], eng["max_len"]
        state = shapes(jax.eval_shape(
            lambda: model.init_decode_state(b, length)))
        # the engine's bodies, taken unbound so nothing is allocated
        self = ServingEngine.__new__(ServingEngine)
        self.model, self._batch_axes_cache = model, None
        progs = {"decode": (jax.jit(self._decode_all), (
            params, state, jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                                sharding=chip),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip), None))}
        for bucket in eng["prefill_buckets"]:
            progs[f"prefill_{bucket}"] = (jax.jit(self._prefill_packed), (
                params, state,
                jax.ShapeDtypeStruct((b, bucket), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip), None))
        n_ref = cell["check"]["requests"]
        width = -(-(cell["traffic"].get("prompt_len", 256)
                    + cell["traffic"].get("max_new_tokens", 256)) // 128) \
            * 128
        ref = refmod.Reference(ref_cfg)
        x = jax.ShapeDtypeStruct((n_ref, width, ref_cfg["d_model"]),
                                 jnp.float32, sharding=chip)
        progs["reference_layer"] = (ref._layer, (
            x, params["layers"], jax.ShapeDtypeStruct((), jnp.int32,
                                                      sharding=chip)))
        for pname, (fn, args) in progs.items():
            ma = fn.lower(*args).compile().memory_analysis()
            print(f"{name} {pname}: arguments {ma.argument_size_in_bytes} "
                  f"outputs {ma.output_size_in_bytes} temps "
                  f"{ma.temp_size_in_bytes} aliased "
                  f"{ma.alias_size_in_bytes} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
