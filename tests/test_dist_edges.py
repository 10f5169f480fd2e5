"""Edge cases for the compressed collectives + elastic reshard round-trip.

Complements the happy-path subprocess tests in test_dist.py: all-zero
gradients, single-device meshes, bf16 inputs, pytree payloads, and the
elastic shrink path through real NamedShardings.  Single-device cases run
in-process; multi-device cases spawn subprocesses with their own XLA_FLAGS.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.compress import (BLOCK, compressed_psum, dequantize_int8,
                                 ef_compress, ef_init, quantize_int8)
from repro.subproc import check_in_subprocess as _run_subprocess


def _single_device_psum(tree):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    specs = jax.tree.map(lambda _: P(), tree)
    f = jax.shard_map(lambda t: compressed_psum(t, "data"), mesh=mesh,
                      in_specs=(specs,), out_specs=specs, check_vma=False)
    return jax.jit(f)(tree)


def test_quantize_int8_edges():
    # all-zero: codes and round-trip are exactly zero
    z = jnp.zeros((3 * BLOCK + 17,))
    q, s, pad = quantize_int8(z)
    assert pad == BLOCK - 17
    assert int(jnp.sum(jnp.abs(q.astype(jnp.int32)))) == 0
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, s, pad,
                                                             z.shape)), 0.0)
    # shorter than one block, and an exact block boundary
    for n in (5, BLOCK):
        x = jnp.asarray(np.random.default_rng(n).normal(size=(n,)),
                        jnp.float32)
        q, s, pad = quantize_int8(x)
        back = dequantize_int8(q, s, pad, x.shape)
        assert back.shape == x.shape
        assert float(jnp.max(jnp.abs(back - x))) <= float(s.max()) / 2 + 1e-7


def test_compressed_psum_single_device_tree():
    """On a 1-device mesh the shared grid is the local grid: zeros stay
    exactly zero, live values round-trip within scale/2, dtypes survive."""
    tree = {
        "zero": jnp.zeros((2, 513)),
        "bf16": jnp.asarray(
            np.random.default_rng(0).normal(size=(129,)), jnp.bfloat16),
        "f32": jnp.asarray(
            np.random.default_rng(1).normal(size=(7, 33)), jnp.float32),
    }
    out = _single_device_psum(tree)
    assert out["bf16"].dtype == jnp.bfloat16
    assert out["f32"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out["zero"]), 0.0)
    for name in ("bf16", "f32"):
        x = np.asarray(tree[name], np.float32)
        got = np.asarray(out[name], np.float32)
        scale = np.abs(x).max() / 127.0
        # bf16 storage adds its own rounding on top of the int8 grid
        tol = scale / 2 + (0.02 if name == "bf16" else 1e-6)
        assert np.max(np.abs(got - x)) <= tol, name


def test_compressed_psum_tree_multidevice_subprocess():
    """4-device all-reduce of a pytree: zeros exact, normals <2% rel, bf16
    dtype preserved."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.compress import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
        tree = {
            "g": jax.random.normal(jax.random.PRNGKey(0), (4, 2, 4096)),
            "z": jnp.zeros((4, 31)),
            "h": jax.random.normal(jax.random.PRNGKey(1),
                                   (4, 1000)).astype(jnp.bfloat16),
        }

        def f(t):
            local = jax.tree.map(lambda x: x[0], t)
            return compressed_psum(local, "data")

        got = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("data"), tree),),
            out_specs=jax.tree.map(lambda _: P(), tree)))(tree)
        assert got["h"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got["z"]), 0.0)
        for name in ("g", "h"):
            want = np.sum(np.asarray(tree[name], np.float32), axis=0)
            rel = np.max(np.abs(np.asarray(got[name], np.float32) - want)) \\
                / np.max(np.abs(want))
            assert rel < 0.02, (name, rel)
        print("EDGES OK")
    """, devices=4)
    assert "EDGES OK" in out


def test_compressed_psum_zero_block_one_device_subprocess():
    """A block that is all-zero on one device must not coarsen the shared
    grid: small gradients (|x| << 0.5) on the other device survive the
    reduce within the documented n_devices * scale / 2 bound instead of
    rounding to zero against the 1.0 all-zero placeholder."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.compress import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(2,), ("data",))
        small = 1e-3 * jax.random.normal(jax.random.PRNGKey(0), (257,))
        stacked = jnp.stack([jnp.zeros_like(small), small])

        got = jax.jit(jax.shard_map(
            lambda t: compressed_psum(t[0], "data"), mesh=mesh,
            in_specs=(P("data"),), out_specs=P()))(stacked)

        want = np.asarray(small, np.float32)
        scale = np.abs(want).max() / 127.0
        err = np.max(np.abs(np.asarray(got, np.float32) - want))
        assert err <= 2 * scale / 2 + 1e-9, err
        assert np.max(np.abs(np.asarray(got))) > 0, "gradient silently lost"
        print("SPARSE OK")
    """, devices=2)
    assert "SPARSE OK" in out


def test_error_feedback_zero_and_tree():
    """EF on an all-zero gradient is a fixed point; tree structure rides
    through compress/residual untouched."""
    tree = {"a": jnp.zeros((100,)), "b": {"c": jnp.ones((10, 10))}}
    res = ef_init(tree)
    approx, res2 = ef_compress(tree, res)
    assert jax.tree_util.tree_structure(approx) == \
        jax.tree_util.tree_structure(tree)
    np.testing.assert_array_equal(np.asarray(approx["a"]), 0.0)
    np.testing.assert_array_equal(np.asarray(res2["a"]), 0.0)
    np.testing.assert_allclose(np.asarray(approx["b"]["c"]), 1.0, atol=0.01)


def test_compressed_psum_ef_identity_subprocess():
    """EF int8 all-reduce: no gradient mass is lost — the summed reduced
    outputs plus the psum of the final residuals equals the true summed
    gradients exactly (to f32 rounding), over multiple steps."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.compress import compressed_psum_ef
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        rng = np.random.default_rng(0)
        g1 = jnp.asarray(rng.normal(0, 1, (4, 100)).astype(np.float32))
        g2 = jnp.asarray(rng.normal(0, 3e-3, (4, 100)).astype(np.float32))
        f = jax.jit(jax.shard_map(
            lambda g, r: compressed_psum_ef(g, r, "data"), mesh=mesh,
            in_specs=(P("data"), P("data")), out_specs=(P(None), P("data")),
            check_vma=False))
        res = jnp.zeros((4, 100), jnp.float32)
        o1, res = f(g1, res)
        o2, res = f(g2, res)
        true = jnp.sum(g1, 0) + jnp.sum(g2, 0)
        lhs = (o1 + o2)[0] + jnp.sum(res, 0)
        err = float(jnp.max(jnp.abs(lhs - true)))
        assert err < 1e-5, err
        # step 2 alone benefits from the carried residual: the tiny g2 is
        # below step 1's quantization grid, EF keeps it from vanishing
        print("EF IDENTITY OK", err)
    """, devices=4)
    assert "EF IDENTITY OK" in out


def test_dp_int8_step_with_error_feedback_subprocess():
    """--grad-comm int8: the EF residual rides in opt_state, the step
    updates it, and params track the exact psum step closely."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.configs.base import AnalogSpec
        from repro.ft.elastic import build_mesh, plan_for_devices
        from repro.launch.steps import (make_dp_opt_state, make_dp_train_step,
                                        make_optimizer)
        from repro.nn.model import build

        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32", analog=AnalogSpec(enabled=False))
        model = build(cfg)
        opt = make_optimizer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S = 8, 16
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (B, S), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2),
                                              (B, S), 0, cfg.vocab)}
        mesh = build_mesh(plan_for_devices(4, global_batch=B,
                                           model_parallel=1))
        os_psum = make_dp_opt_state(opt, params, mesh, grad_comm="psum")
        p_ref, _, m_ref = jax.jit(make_dp_train_step(
            model, opt, mesh, grad_comm="psum"))(params, os_psum, batch, 0)

        os8 = make_dp_opt_state(opt, params, mesh, grad_comm="int8")
        step8 = jax.jit(make_dp_train_step(model, opt, mesh,
                                           grad_comm="int8"))
        p8, os8, m8 = step8(params, os8, batch, 0)
        assert abs(float(m8["loss"] - m_ref["loss"])) < 1e-5
        dmax = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                   zip(jax.tree.leaves(p8), jax.tree.leaves(p_ref)))
        assert dmax < 1e-4, dmax
        res_norm = max(float(jnp.max(jnp.abs(r)))
                       for r in jax.tree.leaves(os8["ef"]))
        assert res_norm > 0, "residual never populated"
        p8b, os8, m8b = step8(p8, os8, batch, 1)   # carried residual runs
        print("DP INT8 EF OK", dmax, res_norm)
    """, devices=4)
    assert "DP INT8 EF OK" in out


def test_dp_step_matches_plain_uneven_masking_subprocess():
    """The explicit-collective DP step must equal the plain (GSPMD-style)
    step when -1-masked labels are unevenly distributed across data shards:
    shards are weighted by valid-token share (zero-valid shards count 0,
    not the clamped 1), so loss/tokens/grad_norm and the updated params all
    match the global normalization."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.configs.base import AnalogSpec
        from repro.ft.elastic import build_mesh, plan_for_devices
        from repro.launch.steps import (make_dp_train_step, make_optimizer,
                                        make_train_step)
        from repro.nn.model import build

        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32", analog=AnalogSpec(enabled=False))
        model = build(cfg)
        opt = make_optimizer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)

        B, S = 8, 16
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                    cfg.vocab)
        # heavy masking on the first half of the batch: some data shards
        # end up with almost no (possibly zero) valid tokens
        mask = jnp.concatenate(
            [jax.random.bernoulli(jax.random.PRNGKey(3), 0.9, (B // 2, S)),
             jax.random.bernoulli(jax.random.PRNGKey(4), 0.1, (B // 2, S))])
        batch = {"tokens": tokens, "labels": jnp.where(mask, -1, labels)}

        p1, _, m1 = jax.jit(make_train_step(model, opt))(
            params, opt_state, batch, 0)
        mesh = build_mesh(plan_for_devices(4, global_batch=B,
                                           model_parallel=1))
        p2, _, m2 = jax.jit(make_dp_train_step(model, opt, mesh,
                                               grad_comm="psum"))(
            params, opt_state, batch, 0)

        assert float(m1["tokens"]) == float(m2["tokens"]), (m1, m2)
        assert abs(float(m1["loss"] - m2["loss"])) < 1e-5, (m1, m2)
        assert abs(float(m1["grad_norm"] - m2["grad_norm"])) < 1e-4
        dmax = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                   zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert dmax < 1e-5, dmax
        print("DP MASKING OK")
    """, devices=4)
    assert "DP MASKING OK" in out


def test_elastic_reshard_roundtrip_subprocess():
    """Shrink 8 -> 4 devices through plan_for_devices + real NamedShardings:
    values are preserved and the new placement matches the new mesh."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.ft.elastic import build_mesh, plan_for_devices, reshard
        from repro.dist import sharding as SH

        params = {
            "mlp": {"wi_gate": {"w": jnp.arange(64.0 * 32).reshape(64, 32)},
                    "wo": {"w": jnp.ones((32, 64))}},
            "norm": {"scale": jnp.arange(64.0)},
        }
        host = jax.tree.map(np.asarray, params)

        plan8 = plan_for_devices(8, global_batch=16, model_parallel=4)
        mesh8 = build_mesh(plan8)
        assert dict(mesh8.shape) == {"data": 2, "model": 4}
        p8 = reshard(params, mesh8)
        spec = p8["mlp"]["wi_gate"]["w"].sharding.spec
        assert tuple(spec) == (None, "model"), spec

        # shrink: 5 surviving devices -> largest fitting (data, model) grid
        plan5 = plan_for_devices(5, global_batch=16, model_parallel=4)
        mesh5 = build_mesh(plan5)
        n5 = plan5.new_shape["data"] * plan5.new_shape["model"]
        assert n5 <= 5 and 16 % plan5.new_shape["data"] == 0
        p5 = reshard(p8, mesh5)
        for a, b in zip(jax.tree.leaves(jax.tree.map(np.asarray, p5)),
                        jax.tree.leaves(host)):
            np.testing.assert_array_equal(a, b)
        assert len(p5["mlp"]["wi_gate"]["w"].sharding.device_set) <= n5
        print("RESHARD OK")
    """, devices=8)
    assert "RESHARD OK" in out


def test_train_state_made_in_place_on_mesh_subprocess():
    """Params and optimizer state are made directly in their layout on the
    mesh, gspmd (megatron, model axis 4) and DP (data axis 4, with the int8
    residual): nothing lands whole on the first device, and the params are
    bitwise the single-device ``model.init``."""
    out = _run_subprocess("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.configs.base import AnalogSpec
        from repro.ft.elastic import build_mesh, init_sharded, plan_for_devices
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_dp_opt_state, make_optimizer
        from repro.nn.model import build

        cfg = configs.get_smoke("qwen2.5-3b").replace(
            analog=AnalogSpec(enabled=False))
        model = build(cfg)
        opt = make_optimizer(cfg)
        key = jax.random.PRNGKey(0)
        host = jax.tree.map(np.asarray, model.init(key))
        dp = build_mesh(plan_for_devices(4, global_batch=8, model_parallel=1))
        for mesh, comm in ((make_host_mesh(), "gspmd"), (dp, "psum"),
                           (dp, "int8")):
            params = init_sharded(model.init, key, mesh)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(host)):
                np.testing.assert_array_equal(np.asarray(a), b)
            state = make_dp_opt_state(opt, params, mesh, grad_comm=comm)
            for leaf in jax.tree.leaves((params, state)):
                assert len(leaf.sharding.device_set) == 4, (comm, leaf.shape)
            adam = state["opt"] if comm == "int8" else state
            for p, m in zip(jax.tree.leaves(params),
                            jax.tree.leaves(adam.mu)):
                assert m.sharding == p.sharding, (comm, m.sharding)
            if comm == "gspmd":
                assert any(p.sharding.spec != P()
                           for p in jax.tree.leaves(params))
            if comm == "int8":
                for r in jax.tree.leaves(state["ef"]):
                    assert r.sharding.spec == P(("data",)), r.sharding
        print("IN PLACE OK")
    """, devices=4)
    assert "IN PLACE OK" in out
