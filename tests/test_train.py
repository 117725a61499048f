"""Training substrate: optimizers, loss descent, checkpoint/restart,
elastic mesh restore, gradient compression."""
import os
import shutil

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from conftest import run_with_devices
from repro.configs import get_smoke_config
from repro.train import optimizer as OPT
from repro.train.checkpoint import (CheckpointManager, latest_step,
                                    restore_checkpoint, save_checkpoint)
from repro.train.trainer import TrainConfig, Trainer


class TestOptimizers:
    @pytest.mark.parametrize("kind", ["adamw", "adafactor"])
    def test_descends_quadratic(self, kind):
        opt = OPT.make_optimizer(kind)
        params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state = opt.update(grads, state, params, 0.05)
        assert float(jnp.abs(params["w"]).max()) < 0.5

    def test_adamw_matrix_decay_only(self):
        params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
        state = OPT.adamw_init(params)
        g = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
        p2, _ = OPT.adamw_update(g, state, params, 0.1, weight_decay=0.5)
        assert float(p2["w"][0, 0]) < 1.0      # decayed
        assert float(p2["b"][0]) == 1.0        # not decayed

    def test_clip_global_norm(self):
        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = OPT.clip_by_global_norm(g, 1.0)
        total = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
        assert abs(total - 1.0) < 1e-5

    def test_warmup_cosine(self):
        lr = OPT.warmup_cosine(1.0, 10, 100)
        assert float(lr(0)) == 0.0
        assert abs(float(lr(10)) - 1.0) < 0.11
        assert float(lr(100)) < float(lr(50))

    def test_int8_roundtrip_error(self, rng):
        x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
        q, s = OPT.quantize_int8(x)
        xr = OPT.dequantize_int8(q, s)
        rel = float(jnp.abs(xr - x).max() / jnp.abs(x).max())
        assert rel < 1.0 / 127 + 1e-3

    def test_compressed_psum_multidevice(self):
        run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.train.optimizer import compressed_psum
mesh = Mesh(np.array(jax.devices()).reshape(4), ("pod",))
x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8) / 7.0

def f(xs):
    return compressed_psum(xs[0], "pod", bits=8)[None]

y = jax.shard_map(f, mesh=mesh, in_specs=(P("pod", None),),
                  out_specs=P("pod", None), check_vma=False)(x)
ref = x.sum(0)
err = float(jnp.abs(np.asarray(y)[0] - ref).max())
assert err < 0.2, err
print("SUBPROCESS_OK")
""", 4)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path, rng):
        tree = {"a": jnp.asarray(rng.standard_normal((4, 5)), jnp.float32),
                "b": {"c": jnp.arange(7)}}
        save_checkpoint(str(tmp_path), 3, tree)
        like = jax.eval_shape(lambda: tree)
        restored, man = restore_checkpoint(str(tmp_path), 3, like)
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                      np.asarray(tree["b"]["c"]))
        assert man["step"] == 3

    def test_latest_and_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), every=1, keep=2)
        tree = {"x": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            mgr.maybe_save(s, tree)
        assert latest_step(str(tmp_path)) == 4
        steps = sorted(int(n[5:]) for n in os.listdir(tmp_path)
                       if n.startswith("step_"))
        assert steps == [3, 4]

    def test_corrupt_tmp_ignored(self, tmp_path):
        os.makedirs(tmp_path / "step_9.tmp")
        save_checkpoint(str(tmp_path), 1, {"x": jnp.zeros(2)})
        assert latest_step(str(tmp_path)) == 1

    def test_shape_mismatch_raises(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"x": jnp.zeros(2)})
        like = jax.eval_shape(lambda: {"x": jnp.zeros(3)})
        with pytest.raises(ValueError):
            restore_checkpoint(str(tmp_path), 1, like)


class TestTrainerFT:
    def _mk(self, tmp, steps=40):
        mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
        cfg = get_smoke_config("qwen2_5_3b")
        tc = TrainConfig(lr=1e-3, warmup=5, total_steps=steps,
                         ckpt_dir=str(tmp), ckpt_every=5, log_every=100)
        return Trainer(cfg, tc, mesh, seq_len=24, global_batch=4)

    def test_loss_descends(self, tmp_path):
        tr = self._mk(tmp_path)
        out = tr.fit(25)
        first = np.mean(out["losses"][:3])
        last = np.mean(out["losses"][-3:])
        assert last < first, (first, last)

    def test_kill_and_restart_resumes_exactly(self, tmp_path):
        """Fault tolerance: a fresh Trainer (simulated restart after crash)
        resumes from the checkpoint and continues the same trajectory."""
        tr1 = self._mk(tmp_path)
        out1 = tr1.fit(10)                    # ckpt at step 10
        # crash: throw away the trainer; build a brand-new one
        tr2 = self._mk(tmp_path)
        out2 = tr2.fit(12)                    # resumes at 10, runs 10..11
        assert len(out2["losses"]) == 2
        # determinism: a run straight to 12 gives the same final loss
        shutil.rmtree(tmp_path)
        tr3 = self._mk(tmp_path)
        out3 = tr3.fit(12)
        np.testing.assert_allclose(out2["losses"][-1], out3["losses"][-1],
                                   rtol=1e-4, atol=1e-5)

    def test_elastic_mesh_restore(self, tmp_path):
        """Save on a (2,2) mesh, restore on (4,1): checkpoints are logical
        arrays, re-laid-out onto whatever mesh the restarted job has."""
        run_with_devices(f"""
import numpy as np, jax, shutil
from jax.sharding import Mesh
from repro.configs import get_smoke_config
from repro.train.trainer import Trainer, TrainConfig

cfg = get_smoke_config("qwen2_5_3b")
tmp = "{tmp_path}/elastic"
mesh1 = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
tc = TrainConfig(lr=1e-3, warmup=2, total_steps=10, ckpt_dir=tmp,
                 ckpt_every=4, log_every=100)
t1 = Trainer(cfg, tc, mesh1, seq_len=16, global_batch=4)
o1 = t1.fit(6)

mesh2 = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))
t2 = Trainer(cfg, tc, mesh2, seq_len=16, global_batch=4)
o2 = t2.fit(8)          # resumes the step-6 final ckpt on the NEW mesh
assert len(o2["losses"]) == 2
assert all(np.isfinite(o2["losses"]))
print("SUBPROCESS_OK")
""", 4)


class TestData:
    def test_deterministic_across_restart(self):
        from repro.data.pipeline import SyntheticLM
        d1 = SyntheticLM(100, 16, 4, seed=7)
        d2 = SyntheticLM(100, 16, 4, seed=7)
        b1 = d1.batch(13)
        b2 = d2.batch(13)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        np.testing.assert_array_equal(b1["labels"], b2["labels"])

    def test_labels_are_shifted_tokens(self):
        from repro.data.pipeline import SyntheticLM
        b = SyntheticLM(50, 8, 2, seed=1).batch(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_has_learnable_structure(self):
        from repro.data.pipeline import SyntheticLM
        b = SyntheticLM(1000, 512, 8, seed=0, structure=0.5).batch(0)
        t = b["tokens"]
        copies = (t[:, 2:] == t[:, :-2]).mean()
        assert copies > 0.3
