"""HLO analyzer correctness (vs known-FLOPs jitted programs), synthetic
data pipeline properties, sharding rule resolution."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data import synthetic
from repro.distributed import hlo_analysis, sharding


class TestHloAnalyzer:
    def test_single_matmul_flops(self):
        m, k, n = 64, 128, 32
        f = jax.jit(lambda a, b: a @ b)
        txt = f.lower(jnp.ones((m, k)), jnp.ones((k, n))).compile().as_text()
        t = hlo_analysis.analyze(txt)
        assert np.isclose(t.flops, 2 * m * k * n, rtol=1e-6)

    def test_scan_multiplies_trip_count(self):
        """The core property cost_analysis() lacks: a lax.scan of T matmuls
        must count T times the body FLOPs."""
        m = 32
        T = 7

        def step(x, w):
            return x @ w, ()

        def fn(x, ws):
            y, _ = jax.lax.scan(step, x, ws)
            return y

        txt = jax.jit(fn).lower(
            jnp.ones((m, m)), jnp.ones((T, m, m))).compile().as_text()
        t = hlo_analysis.analyze(txt)
        assert np.isclose(t.flops, T * 2 * m ** 3, rtol=0.01), t.flops

    def test_nested_scan(self):
        m, t_in, t_out = 16, 3, 5

        def inner(x, w):
            return x @ w, ()

        def outer(x, ws):
            def body(c, _):
                y, _ = jax.lax.scan(inner, c, ws)
                return y, ()
            y, _ = jax.lax.scan(body, x, None, length=t_out)
            return y

        txt = jax.jit(outer).lower(
            jnp.ones((m, m)), jnp.ones((t_in, m, m))).compile().as_text()
        t = hlo_analysis.analyze(txt)
        assert np.isclose(t.flops, t_out * t_in * 2 * m ** 3, rtol=0.01)

    def test_trip_count_from_synthetic_hlo(self):
        hlo = """
HloModule test

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  %c = s32[] constant(9)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %x = f32[4] get-tuple-element(%p), index=1
  %ar = f32[4] all-reduce(%x), to_apply=%add
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4]) tuple(%i, %ar)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[4]) tuple(%zero, %a)
  %w = (s32[], f32[4]) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[4] get-tuple-element(%w), index=1
}
"""
        t = hlo_analysis.analyze(hlo)
        assert t.coll_counts.get("all-reduce") == 9
        assert np.isclose(t.coll_bytes["all-reduce"], 9 * 16)

    def test_roofline_terms_and_dominance(self):
        r = hlo_analysis.Roofline(
            flops_per_device=197e12, bytes_per_device=819e9 / 2,
            collective_bytes=50e9 * 3, n_devices=256)
        assert np.isclose(r.compute_s, 1.0)
        assert np.isclose(r.memory_s, 0.5)
        assert np.isclose(r.collective_s, 3.0)
        assert r.dominant == "collective"
        assert np.isclose(r.step_s, 3.0)


class TestSyntheticData:
    def test_batches_deterministic(self):
        x1, y1 = synthetic.class_batch(synthetic.CIFAR10_LIKE, 5, 16, 0)
        x2, y2 = synthetic.class_batch(synthetic.CIFAR10_LIKE, 5, 16, 0)
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
        x3, _ = synthetic.class_batch(synthetic.CIFAR10_LIKE, 6, 16, 0)
        assert not np.allclose(np.asarray(x1), np.asarray(x3))

    def test_templates_do_not_depend_on_process_hash(self, monkeypatch):
        """Python's str hash is salted per process; the data (and the
        compiled search step it is baked into) must not be."""
        spec = synthetic.CIFAR10_LIKE
        before = np.asarray(synthetic._templates(spec))
        monkeypatch.setattr(synthetic, "hash", lambda _: 12345,
                            raising=False)
        np.testing.assert_array_equal(
            np.asarray(synthetic._templates(spec)), before)

    def test_class_structure_learnable(self):
        """Same-class samples are closer to their template than to others
        (so the dataset is actually learnable)."""
        spec = synthetic.CIFAR10_LIKE
        temps = np.asarray(synthetic._templates(spec))
        x, y = synthetic.class_batch(spec, 0, 64, 0)
        x, y = np.asarray(x), np.asarray(y)
        correct = 0
        for i in range(64):
            d = [np.linalg.norm(
                np.roll(x[i], s, axis=1) - temps[c])
                for c in range(spec.num_classes) for s in (-2, -1, 0, 1, 2)]
            d = np.asarray(d).reshape(spec.num_classes, 5).min(1)
            correct += int(np.argmin(d) == y[i])
        assert correct / 64 > 0.9

    def test_lm_batch_structure(self):
        b = synthetic.lm_batch(512, 33, 4, step=0)
        assert b["tokens"].shape == (4, 32)
        assert b["targets"].shape == (4, 32)
        # mostly follows the affine recurrence (structure=0.9)
        toks = np.asarray(b["tokens"])
        tgts = np.asarray(b["targets"])
        matches = 0
        for a in (3, 5, 7, 11):
            for bb in range(13):
                m = (tgts == (a * toks + bb) % 512).mean(axis=1)
                matches = max(matches, float(m.max()))
        assert matches > 0.7

    def test_shapes_match_paper_benchmarks(self):
        assert synthetic.CIFAR10_LIKE.shape == (32, 32, 3)
        assert synthetic.GSC_LIKE.num_classes == 12
        assert synthetic.TINYIMAGENET_LIKE.num_classes == 200


class TestShardingRules:
    def test_rules_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        assert sharding.constrain(x, "batch", None) is x
        assert sharding.spec("batch", "embed") == \
            jax.sharding.PartitionSpec()

    def test_use_mesh_filters_absent_axes(self):
        mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
        with sharding.use_mesh(mesh, {}) as rules:
            # 'pod'/'model' don't exist on this mesh -> dropped
            assert rules["batch"] == ("data",)
            assert rules["heads"] is None

    def test_spec_resolution(self):
        mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
        with sharding.use_mesh(mesh, {"embed": "data"}):
            s = sharding.spec("batch", "embed", None)
            assert s == jax.sharding.PartitionSpec(("data",), "data", None)


class TestMicrobatchAccumulation:
    def test_microbatched_step_matches_full_batch(self):
        """k-microbatch gradient accumulation must equal the full-batch
        step (same mean gradient) up to accumulation-order rounding."""
        import dataclasses
        from repro.configs import registry
        from repro.launch import steps as steps_lib
        from repro.models import lm
        from repro.optim import optimizers

        base = registry.reduced(registry.ARCHS["llama3.2-1b"])
        cfg1 = dataclasses.replace(base, train_microbatches=1)
        cfg2 = dataclasses.replace(base, train_microbatches=2)
        params = lm.init_params(cfg1, jax.random.key(0))
        opt = optimizers.make_optimizer("adam", 1e-3)
        state = opt.init(params)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32),
                                              0, cfg1.vocab),
                 "targets": jax.random.randint(jax.random.key(2), (4, 32),
                                               0, cfg1.vocab)}
        s1 = steps_lib.make_train_step(cfg1, opt)
        s2 = steps_lib.make_train_step(cfg2, opt)
        p1, _, l1 = s1(params, state, batch, jnp.asarray(0))
        p2, _, l2 = s2(params, state, batch, jnp.asarray(0))
        assert np.isclose(float(l1), float(l2), rtol=1e-3)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-3)
