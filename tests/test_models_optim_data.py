import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from draco_tpu import models, optim
from draco_tpu.data import augment, batching, datasets


class TestModels:
    @pytest.mark.parametrize(
        "name,shape",
        [
            ("LeNet", (28, 28, 1)),
            ("FC", (28, 28, 1)),
            ("ResNet18", (32, 32, 3)),
            ("VGG11", (32, 32, 3)),
            ("VGG11_bn", (32, 32, 3)),
        ],
    )
    def test_forward_shapes(self, name, shape):
        model = models.build_model(name)
        x = jnp.zeros((2,) + shape)
        # init and forward pass ONE compiled program each: called eagerly
        # they are a dispatch and a tiny compile a primitive
        variables = jax.jit(lambda p, d: model.init(
            {"params": p, "dropout": d}, x, train=False
        ))(jax.random.key(0), jax.random.key(1))
        out = jax.jit(lambda v: model.apply(v, x, train=False))(variables)
        assert out.shape == (2, 10)

    def test_resnet18_param_count(self):
        # CIFAR ResNet-18 has ~11.17M parameters — sanity against the standard
        model = models.build_model("ResNet18")
        v = jax.eval_shape(lambda k: model.init(
            k, jnp.zeros((1, 32, 32, 3)), train=False), jax.random.key(0))
        n = sum(np.prod(p.shape) for p in jax.tree.leaves(v["params"]))
        assert 11_000_000 < n < 11_400_000

    def test_lenet_param_count(self):
        # 20*25+20 + 50*20*25+50 + 800*500+500 + 500*10+10 = 431080
        model = models.build_model("LeNet")
        v = jax.eval_shape(lambda k: model.init(
            k, jnp.zeros((1, 28, 28, 1)), train=False), jax.random.key(0))
        n = sum(np.prod(p.shape) for p in jax.tree.leaves(v["params"]))
        assert n == 431080

    def test_heavy_models_build(self):
        # trace-only (init shapes) for the rest of the zoo
        for name in ("ResNet34", "VGG13", "VGG16"):
            model = models.build_model(name)
            out, _ = jax.eval_shape(
                lambda m=model: m.init_with_output(
                    {"params": jax.random.key(0), "dropout": jax.random.key(1)},
                    jnp.zeros((1, 32, 32, 3)),
                    train=False,
                )
            )
            assert out.shape == (1, 10)

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError):
            models.build_model("AlexNet")


class TestOptim:
    def test_sgd_matches_torch(self, rng):
        import torch

        w0 = rng.randn(7, 3).astype(np.float32)
        grads = [rng.randn(7, 3).astype(np.float32) for _ in range(5)]

        tp = torch.nn.Parameter(torch.tensor(w0.copy()))
        topt = torch.optim.SGD([tp], lr=0.1, momentum=0.9)
        for g in grads:
            tp.grad = torch.tensor(g)
            topt.step()

        jopt = optim.sgd_modified(lr=0.1, momentum=0.9)
        params = {"w": jnp.asarray(w0)}
        state = jopt.init(params)
        for g in grads:
            updates, state = jopt.update({"w": jnp.asarray(g)}, state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        np.testing.assert_allclose(np.asarray(params["w"]), tp.detach().numpy(), rtol=1e-5, atol=1e-6)

    def test_adam_matches_torch(self, rng):
        import torch

        w0 = rng.randn(4, 4).astype(np.float32)
        grads = [rng.randn(4, 4).astype(np.float32) for _ in range(4)]

        tp = torch.nn.Parameter(torch.tensor(w0.copy()))
        topt = torch.optim.Adam([tp], lr=0.01)
        for g in grads:
            tp.grad = torch.tensor(g)
            topt.step()

        jopt = optim.adam_modified(lr=0.01)
        params = {"w": jnp.asarray(w0)}
        state = jopt.init(params)
        for g in grads:
            updates, state = jopt.update({"w": jnp.asarray(g)}, state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        np.testing.assert_allclose(np.asarray(params["w"]), tp.detach().numpy(), rtol=1e-4, atol=1e-6)


    def test_adamw_matches_torch(self, rng):
        import torch

        w0 = rng.randn(4, 4).astype(np.float32)
        grads = [rng.randn(4, 4).astype(np.float32) for _ in range(4)]

        tp = torch.nn.Parameter(torch.tensor(w0.copy()))
        topt = torch.optim.AdamW([tp], lr=0.01, weight_decay=0.05)
        for g in grads:
            tp.grad = torch.tensor(g)
            topt.step()

        jopt = optim.adamw_modified(lr=0.01, weight_decay=0.05)
        params = {"w": jnp.asarray(w0)}
        state = jopt.init(params)
        for g in grads:
            updates, state = jopt.update({"w": jnp.asarray(g)}, state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        np.testing.assert_allclose(np.asarray(params["w"]), tp.detach().numpy(),
                                   rtol=1e-4, atol=1e-6)


    def test_opt_state_structure_invariant_across_schedules(self):
        """Resuming a checkpoint across a schedule-family switch requires the
        opt-state pytree structure not to depend on the family (r3 advisor
        finding): constant is built as a degenerate schedule inside the same
        chain, with or without clip_norm (a stateless wrapper)."""
        params = {"w": jnp.zeros((3,))}
        structures = {
            jax.tree.structure(
                optim.build_optimizer("sgd", 0.1, momentum=0.9,
                                      schedule=schedule, total_steps=100,
                                      clip_norm=clip).init(params)
            )
            for schedule in ("constant", "cosine")
            for clip in (0.0, 1.0)
        }
        assert len(structures) == 1

    def test_constant_schedule_build_matches_bare_rule(self, rng):
        """The degenerate-constant chain must update identically to the bare
        torch-parity rule it wraps."""
        w0 = rng.randn(5, 2).astype(np.float32)
        grads = [rng.randn(5, 2).astype(np.float32) for _ in range(4)]
        results = []
        for opt in (optim.build_optimizer("sgd", 0.1, momentum=0.9,
                                          schedule="constant"),
                    optim.sgd_modified(lr=0.1, momentum=0.9)):
            params = {"w": jnp.asarray(w0)}
            state = opt.init(params)
            for g in grads:
                updates, state = opt.update({"w": jnp.asarray(g)}, state, params)
                params = jax.tree.map(lambda p, u: p + u, params, updates)
            results.append(np.asarray(params["w"]))
        np.testing.assert_allclose(results[0], results[1], rtol=1e-6, atol=1e-7)

    def test_cosine_schedule_shape(self):
        sched = optim.lr_schedule("cosine", lr=0.1, warmup_steps=10,
                                  total_steps=110)
        # warmup ramps linearly to peak
        np.testing.assert_allclose(float(sched(0)), 0.01, rtol=1e-5)
        np.testing.assert_allclose(float(sched(9)), 0.1, rtol=1e-5)
        # peak right after warmup, floor (10% of peak) at the end
        np.testing.assert_allclose(float(sched(10)), 0.1, rtol=1e-5)
        np.testing.assert_allclose(float(sched(110)), 0.01, rtol=1e-4)
        # monotone decay in between
        vals = [float(sched(t)) for t in range(10, 111, 10)]
        assert vals == sorted(vals, reverse=True)

    def test_scheduled_sgd_equals_manual_lr_sequence(self, rng):
        """A scheduled rule must match running the fixed-lr rule with the
        schedule's rate at each step — the composition contract."""
        w0 = rng.randn(3, 3).astype(np.float32)
        grads = [rng.randn(3, 3).astype(np.float32) for _ in range(5)]
        sched = optim.lr_schedule("cosine", lr=0.1, warmup_steps=2,
                                  total_steps=5)

        opt = optim.build_optimizer("sgd", lr=0.1, momentum=0.9,
                                    schedule="cosine", warmup_steps=2,
                                    total_steps=5)
        params = {"w": jnp.asarray(w0)}
        state = opt.init(params)
        for g in grads:
            updates, state = opt.update({"w": jnp.asarray(g)}, state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)

        # manual: same momentum buffer algebra, rate applied per step
        buf = np.zeros_like(w0)
        w = w0.copy()
        for t, g in enumerate(grads):
            buf = g if t == 0 else 0.9 * buf + g
            w = w - float(sched(t)) * buf
        np.testing.assert_allclose(np.asarray(params["w"]), w, rtol=1e-5,
                                   atol=1e-6)


    def test_clip_norm_bounds_update(self, rng):
        """clip 1.0 on a huge gradient: the sgd (lr=1, no momentum) update's
        global norm equals the clip; a small gradient passes untouched."""
        big = {"w": jnp.full((4, 4), 100.0)}
        small = {"w": jnp.full((4, 4), 1e-3)}
        opt = optim.build_optimizer("sgd", lr=1.0, momentum=0.0,
                                    clip_norm=1.0)
        state = opt.init(big)
        up, _ = opt.update(big, state, big)
        np.testing.assert_allclose(
            float(optax.global_norm(up)), 1.0, rtol=1e-5)
        up, _ = opt.update(small, state, small)
        np.testing.assert_allclose(np.asarray(up["w"]),
                                   -np.asarray(small["w"]), rtol=1e-6)


class TestData:
    def test_synthetic_fallback_shapes(self):
        ds = datasets.load_dataset("synthetic-mnist", synthetic_train=256, synthetic_test=64)
        assert ds.train_x.shape == (256, 28, 28, 1)
        assert ds.synthetic
        ds = datasets.load_dataset("Cifar10", data_dir="/nonexistent", synthetic_train=128)
        assert ds.train_x.shape == (128, 32, 32, 3)
        assert ds.name == "synthetic-cifar10"

    def test_synthetic_learnable(self):
        # a nearest-prototype probe must beat chance by a wide margin
        ds = datasets.load_dataset("synthetic-mnist", synthetic_train=2048, synthetic_test=512)
        protos = np.stack([ds.train_x[ds.train_y == c].mean(0) for c in range(10)])
        d = ((ds.test_x[:, None] - protos[None]) ** 2).sum(axis=(2, 3, 4))
        acc = (d.argmin(1) == ds.test_y).mean()
        assert acc > 0.6

    def test_grouped_batches_identical_within_group(self):
        ds = datasets.load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
        seeds = np.array([11, 22, 33])
        x, y = batching.worker_batches_grouped(ds, step=5, num_workers=6, group_size=2,
                                               batch_size=8, seeds=seeds)
        assert x.shape == (6, 8, 28, 28, 1)
        np.testing.assert_array_equal(x[0], x[1])
        np.testing.assert_array_equal(x[2], x[3])
        assert not np.array_equal(x[0], x[2])

    def test_baseline_batches_differ_across_workers(self):
        ds = datasets.load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
        x, y = batching.worker_batches_baseline(ds, step=0, num_workers=4, batch_size=8, seed=428)
        assert not np.array_equal(x[0], x[1])

    def test_cyclic_global_batch_deterministic(self):
        ds = datasets.load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
        x1, y1 = batching.cyclic_global_batch(ds, step=3, num_workers=8, batch_size=4, seed=428)
        x2, y2 = batching.cyclic_global_batch(ds, step=3, num_workers=8, batch_size=4, seed=428)
        np.testing.assert_array_equal(x1, x2)
        assert x1.shape == (8, 4, 28, 28, 1)
        # consecutive steps address disjoint sample ranges within an epoch
        x3, _ = batching.cyclic_global_batch(ds, step=4, num_workers=8, batch_size=4, seed=428)
        assert not np.array_equal(x1, x3)

    def test_augment_shapes_and_determinism(self):
        x = jnp.asarray(np.random.RandomState(0).randn(4, 32, 32, 3).astype(np.float32))
        k = jax.random.key(7)
        a1 = augment.augment_batch(x, k)
        a2 = augment.augment_batch(x, k)
        assert a1.shape == x.shape
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


class TestMixedPrecision:
    """compute_dtype=bfloat16: conv/dense stacks run in bf16 (MXU full rate),
    params/BN stats/logits/gradients stay float32."""

    def test_bf16_grads_are_float32_and_finite(self):
        import jax
        import jax.numpy as jnp

        from draco_tpu.models import build_model

        model = build_model("ResNet18", dtype="bfloat16")
        x = jnp.ones((2, 32, 32, 3), jnp.float32)
        vs = jax.jit(lambda k: model.init(k, x, train=False))(
            jax.random.key(0))
        assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(vs["params"]))

        def loss_fn(params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": vs["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            assert logits.dtype == jnp.float32
            return jnp.mean(logits ** 2)

        g = jax.jit(jax.grad(loss_fn))(vs["params"])
        leaves = jax.tree.leaves(g)
        assert all(p.dtype == jnp.float32 for p in leaves)
        assert all(bool(jnp.all(jnp.isfinite(p))) for p in leaves)

    def test_bf16_cyclic_training_learns(self):
        import numpy as np

        from draco_tpu.config import TrainConfig
        from draco_tpu.data.datasets import load_dataset
        from draco_tpu.runtime import make_mesh
        from draco_tpu.training.trainer import Trainer

        ds = load_dataset("synthetic-mnist", synthetic_train=512, synthetic_test=64)
        cfg = TrainConfig(
            network="LeNet", dataset="synthetic-mnist", batch_size=4,
            num_workers=8, approach="cyclic", worker_fail=1,
            err_mode="rev_grad", redundancy="shared",
            compute_dtype="bfloat16", max_steps=25, eval_freq=0,
            train_dir="", log_every=1000,
        )
        tr = Trainer(cfg, mesh=make_mesh(8), dataset=ds, quiet=True)
        first = tr.run(max_steps=1)
        last = tr.run(max_steps=25)
        assert np.isfinite(last["loss"])
        assert last["loss"] < first["loss"]
        tr.close()


class TestRawFileLoaders:
    """Fixture-backed tests for the raw MNIST/CIFAR file loaders — tiny
    idx-ubyte / cifar-pickle files written to tmp_path, so a format bug can't
    hide until a machine with real data (reference layouts: util.py:23-66)."""

    @staticmethod
    def _write_idx_images(path, arr, gz=False):
        import gzip as _gzip

        payload = (0x00000803).to_bytes(4, "big")
        for d in arr.shape:
            payload += int(d).to_bytes(4, "big")
        payload += arr.tobytes()
        opener = _gzip.open if gz else open
        with opener(path, "wb") as f:
            f.write(payload)

    @staticmethod
    def _write_idx_labels(path, y, gz=False):
        import gzip as _gzip

        payload = (0x00000801).to_bytes(4, "big") + int(len(y)).to_bytes(4, "big")
        payload += y.tobytes()
        opener = _gzip.open if gz else open
        with opener(path, "wb") as f:
            f.write(payload)

    @pytest.mark.parametrize("gz", [False, True])
    def test_mnist_idx_loader(self, tmp_path, gz):
        from draco_tpu.data import datasets as dsm

        r = np.random.RandomState(3)
        tr_x = r.randint(0, 256, size=(8, 28, 28), dtype=np.uint8)
        tr_y = r.randint(0, 10, size=(8,), dtype=np.uint8)
        te_x = r.randint(0, 256, size=(4, 28, 28), dtype=np.uint8)
        te_y = r.randint(0, 10, size=(4,), dtype=np.uint8)
        sfx = ".gz" if gz else ""
        self._write_idx_images(str(tmp_path / f"train-images-idx3-ubyte{sfx}"), tr_x, gz)
        self._write_idx_labels(str(tmp_path / f"train-labels-idx1-ubyte{sfx}"), tr_y, gz)
        self._write_idx_images(str(tmp_path / f"t10k-images-idx3-ubyte{sfx}"), te_x, gz)
        self._write_idx_labels(str(tmp_path / f"t10k-labels-idx1-ubyte{sfx}"), te_y, gz)

        ds = dsm._try_load_mnist(str(tmp_path))
        assert ds is not None and not ds.synthetic and ds.name == "MNIST"
        assert ds.train_x.shape == (8, 28, 28, 1) and ds.train_x.dtype == np.float32
        assert ds.test_x.shape == (4, 28, 28, 1)
        assert ds.train_y.dtype == np.int32 and ds.test_y.dtype == np.int32
        np.testing.assert_array_equal(ds.train_y, tr_y.astype(np.int32))
        # normalisation matches the reference constants (util.py:33)
        want = (tr_x.astype(np.float32) / 255.0 - dsm.MNIST_MEAN) / dsm.MNIST_STD
        np.testing.assert_allclose(ds.train_x[..., 0], want, rtol=1e-6)
        # load_dataset dispatch finds the same files
        ds2 = dsm.load_dataset("MNIST", data_dir=str(tmp_path))
        assert not ds2.synthetic

    def test_cifar10_pickle_loader(self, tmp_path):
        import pickle

        from draco_tpu.data import datasets as dsm

        r = np.random.RandomState(4)
        bdir = tmp_path / "cifar-10-batches-py"
        bdir.mkdir()
        raws, labs = [], []
        for i in range(1, 6):
            raw = r.randint(0, 256, size=(4, 3072), dtype=np.uint8)
            lab = r.randint(0, 10, size=(4,)).tolist()
            raws.append(raw)
            labs.append(lab)
            with open(bdir / f"data_batch_{i}", "wb") as f:
                pickle.dump({b"data": raw, b"labels": lab}, f)
        te_raw = r.randint(0, 256, size=(6, 3072), dtype=np.uint8)
        te_lab = r.randint(0, 10, size=(6,)).tolist()
        with open(bdir / "test_batch", "wb") as f:
            pickle.dump({b"data": te_raw, b"labels": te_lab}, f)

        ds = dsm._try_load_cifar10(str(tmp_path))
        assert ds is not None and not ds.synthetic and ds.name == "Cifar10"
        assert ds.train_x.shape == (20, 32, 32, 3) and ds.train_x.dtype == np.float32
        assert ds.test_x.shape == (6, 32, 32, 3)
        np.testing.assert_array_equal(ds.train_y, np.concatenate(labs).astype(np.int32))
        np.testing.assert_array_equal(ds.test_y, np.asarray(te_lab, np.int32))
        # CHW -> HWC transpose + per-channel normalisation (util.py:37-38)
        want0 = te_raw[0].reshape(3, 32, 32).transpose(1, 2, 0).astype(np.float32) / 255.0
        want0 = (want0 - dsm.CIFAR_MEAN) / dsm.CIFAR_STD
        np.testing.assert_allclose(ds.test_x[0], want0, rtol=1e-5)
