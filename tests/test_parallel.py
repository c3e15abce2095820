"""parallel/ package tests on the virtual 8-device CPU mesh (the analog of
the reference's local[N]-Spark distributed tests, SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from bigdl_tpu.parallel import (
    all_gather, all_reduce, compressed_all_reduce, create_mesh,
    mesh_axis_size, reduce_scatter, ring_attention, shard_batch,
    ulysses_attention, PipelineModule, dp_train_step,
)


def _ref_attention(q, k, v, causal=False):
    d = q.shape[-1]
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = q.shape[1]
        mask = np.tril(np.ones((s, s), bool))
        logits = np.where(mask[None, None], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


class TestMesh:
    def test_create_mesh_dict(self, devices):
        mesh = create_mesh({"data": 4, "model": 2})
        assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2

    def test_create_mesh_infer(self, devices):
        mesh = create_mesh({"data": -1, "model": 2})
        assert mesh.shape["data"] == 4

    def test_axis_size_missing(self, devices):
        mesh = create_mesh({"data": 8})
        assert mesh_axis_size(mesh, "model") == 1

    def test_shard_batch(self, devices):
        mesh = create_mesh({"data": 8})
        x = shard_batch(np.ones((16, 3)), mesh)
        assert x.sharding.spec == P("data")


class TestCollectives:
    def test_all_reduce_and_compressed(self, devices):
        mesh = create_mesh({"data": 8})

        def body(x):
            return (all_reduce(x, "data"),
                    compressed_all_reduce(x, "data"))

        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        f = shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))
        full, comp = f(x)
        np.testing.assert_allclose(np.asarray(full), 28.0 * np.ones((8, 1)))
        np.testing.assert_allclose(np.asarray(comp), 28.0 * np.ones((8, 1)),
                                   rtol=1e-2)

    def test_reduce_scatter_gather_roundtrip(self, devices):
        mesh = create_mesh({"data": 8})
        x = np.random.RandomState(0).rand(8, 8).astype(np.float32)

        def body(xl):
            rs = reduce_scatter(xl, "data", axis=0)   # sum then scatter
            return all_gather(rs, "data", axis=0)

        f = shard_map(body, mesh=mesh, in_specs=P(None, "data"),
                      out_specs=P(None, "data"))
        out = np.asarray(f(x))
        # device d holds column d; rs gives it row-sum d; gather+out_spec
        # tiles the row-sum vector across all 8 columns
        np.testing.assert_allclose(
            out, np.tile(x.sum(1, keepdims=True), (1, 8)), rtol=1e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, devices, causal):
        mesh = create_mesh({"seq": 8})
        rs = np.random.RandomState(1)
        b, s, h, d = 2, 32, 4, 8
        q, k, v = (rs.randn(b, s, h, d).astype(np.float32) for _ in range(3))
        out = np.asarray(ring_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            axis="seq", causal=causal, batch_axis=None))
        ref = _ref_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_2d_mesh_data_and_seq(self, devices):
        mesh = create_mesh({"data": 2, "seq": 4})
        rs = np.random.RandomState(2)
        b, s, h, d = 4, 16, 2, 4
        q, k, v = (rs.randn(b, s, h, d).astype(np.float32) for _ in range(3))
        out = np.asarray(ring_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            axis="seq", causal=True))
        ref = _ref_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, devices, causal):
        mesh = create_mesh({"seq": 4})
        rs = np.random.RandomState(3)
        b, s, h, d = 2, 16, 8, 4
        q, k, v = (rs.randn(b, s, h, d).astype(np.float32) for _ in range(3))
        out = np.asarray(ulysses_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            axis="seq", causal=causal, batch_axis=None))
        ref = _ref_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestPipeline:
    def test_stacked_linear_stages(self, devices):
        mesh = create_mesh({"pipe": 4})
        n_stages, n_micro, mb, dim = 4, 8, 2, 6
        rs = np.random.RandomState(4)
        w = rs.randn(n_stages, dim, dim).astype(np.float32) * 0.3
        b = rs.randn(n_stages, dim).astype(np.float32) * 0.1
        xs = rs.randn(n_micro, mb, dim).astype(np.float32)

        def stage_apply(p, x):
            return jnp.tanh(x @ p["w"].T + p["b"])

        pipe = PipelineModule(stage_apply, n_stages, mesh)
        params = pipe.place_params({"w": jnp.asarray(w), "b": jnp.asarray(b)})
        out = np.asarray(pipe(params, xs))

        ref = xs
        for i in range(n_stages):
            ref = np.tanh(ref @ w[i].T + b[i])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


    def test_pipeline_training_loss_parity(self, devices):
        """GPipe training through the stage ring must track single-device
        training exactly (VERDICT r1: pipeline was forward-only)."""
        from bigdl_tpu.optim.optim_method import SGD
        from bigdl_tpu.parallel import (
            make_pipeline_train_step, split_microbatches)

        mesh = create_mesh({"pipe": 4})
        n_stages, n_micro, mb, dim = 4, 8, 4, 6
        rs = np.random.RandomState(7)
        w0 = rs.randn(n_stages, dim, dim).astype(np.float32) * 0.4
        b0 = rs.randn(n_stages, dim).astype(np.float32) * 0.1
        x = rs.randn(n_micro * mb, dim).astype(np.float32)
        t = np.tanh(x @ rs.randn(dim, dim).astype(np.float32))

        def stage_apply(p, xb):
            return jnp.tanh(xb @ p["w"].T + p["b"])

        def loss_fn(outs, targets):
            return jnp.mean((outs - targets) ** 2)

        optim = SGD(learning_rate=0.2)

        # -- pipeline run ---------------------------------------------------
        pipe = PipelineModule(stage_apply, n_stages, mesh, remat=True)
        params = pipe.place_params(
            {"w": jnp.asarray(w0), "b": jnp.asarray(b0)})
        opt_state = optim.init_state(params)
        step = make_pipeline_train_step(pipe, loss_fn, optim, lr=0.2)
        micro_x = split_microbatches(jnp.asarray(x), n_micro)
        micro_t = split_microbatches(jnp.asarray(t), n_micro)
        pipe_losses = []
        for _ in range(10):
            params, opt_state, loss = step(params, opt_state,
                                           micro_x, micro_t)
            pipe_losses.append(float(loss))

        # -- single-device reference ---------------------------------------
        ref_params = {"w": jnp.asarray(w0), "b": jnp.asarray(b0)}
        ref_opt = optim.init_state(ref_params)

        def ref_loss(p):
            h = jnp.asarray(x)
            for i in range(n_stages):
                h = jnp.tanh(h @ p["w"][i].T + p["b"][i])
            return jnp.mean((h - jnp.asarray(t)) ** 2)

        ref_losses = []
        for _ in range(10):
            l, g = jax.value_and_grad(ref_loss)(ref_params)
            ref_params, ref_opt = optim.step(ref_params, g, ref_opt, 0.2)
            ref_losses.append(float(l))

        np.testing.assert_allclose(pipe_losses, ref_losses,
                                   rtol=1e-4, atol=1e-5)
        assert pipe_losses[-1] < pipe_losses[0] * 0.9, "did not learn"


class TestDpTrainStep:
    def test_linear_regression_converges_sharded(self, devices):
        from bigdl_tpu.optim.optim_method import SGD

        mesh = create_mesh({"data": 8})
        rs = np.random.RandomState(5)
        w_true = rs.randn(3).astype(np.float32)
        x = rs.randn(64, 3).astype(np.float32)
        y = x @ w_true

        def apply_fn(p, s, xb, rng):
            return xb @ p["w"], s

        def loss_fn(pred, t):
            return jnp.mean((pred - t) ** 2)

        optim = SGD(learning_rate=0.1)
        step = dp_train_step(apply_fn, loss_fn, optim, mesh)
        params = {"w": jax.device_put(jnp.zeros(3),
                                      NamedSharding(mesh, P()))}
        opt_state = optim.init_state(params)
        xs = shard_batch(x, mesh)
        ys = shard_batch(y, mesh)
        loss = None
        for _ in range(200):
            params, _, opt_state, loss = step(
                params, {}, opt_state, xs, ys, 0.1, jax.random.PRNGKey(0))
        assert float(loss) < 1e-4
        np.testing.assert_allclose(np.asarray(params["w"]), w_true,
                                   atol=1e-2)


class TestQuantizedAllReduce:
    def test_matches_exact_allreduce(self, devices):
        """EQuARX-style int8 wire allreduce over the 8-device mesh must
        approximate the exact psum within the per-block quantization
        bound."""
        import functools
        from jax.sharding import Mesh
        from bigdl_tpu.parallel import quantized_all_reduce

        mesh = Mesh(np.asarray(devices), ("d",))
        rs = np.random.RandomState(0)
        x = rs.randn(8, 64, 37).astype(np.float32)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        def qar(xs):
            return quantized_all_reduce(xs[0], "d")[None]

        out = np.asarray(jax.jit(qar)(x))
        exact = x.sum(axis=0)
        # every shard holds the same (approximate) sum
        for i in range(8):
            err = np.abs(out[i] - exact).max()
            scale = np.abs(exact).max()
            assert err / scale < 0.05, err / scale

    def test_mean_and_dtype_roundtrip(self, devices):
        import functools
        from jax.sharding import Mesh
        from bigdl_tpu.parallel import quantized_all_reduce

        mesh = Mesh(np.asarray(devices), ("d",))
        x = np.ones((8, 130), np.float32) * 3.0   # non-multiple of block

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        def qar(xs):
            t = {"g": xs[0].astype(jnp.bfloat16)}
            return quantized_all_reduce(t, "d", mean=True)["g"][None]

        out = np.asarray(jax.jit(qar)(x), np.float32)
        np.testing.assert_allclose(out, 3.0, rtol=0.02)
