"""Distributed runtime tests: trainer, checkpoint/restart, elastic restore,
gradient compression, sharding rules.  Single-device (mesh 1×1) so the pjit
code paths run on CPU."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_latest, save
from repro.distributed.shardings import (
    LM_RULES,
    axis_rules,
    logical_to_spec,
    spec_tree,
)
from repro.launch.mesh import single_device_mesh
from repro.launch.train import TrainConfig, Trainer
from repro.models import transformer_lm as lm
from repro.optim import dequantize_int8, quantize_int8

CFG = lm.LMConfig(
    name="tiny", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
    d_ff=64, vocab=101, dtype="float32", kv_block=8,
)


def make_batch(step):
    k = jax.random.PRNGKey(1000 + step)
    toks = jax.random.randint(k, (4, 16), 0, 101)
    return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}


def test_trainer_loss_decreases():
    tc = TrainConfig(steps=30, warmup=3, log_every=1,
                     adamw=dataclasses.replace(TrainConfig().adamw, lr=3e-3))
    tr = Trainer(lm, CFG, train_cfg=tc)

    def fixed_batch(step):
        return make_batch(0)  # overfit one batch

    _, _, hist = tr.fit(fixed_batch, steps=30)
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.05


def test_restart_bit_identical():
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(steps=12, ckpt_every=5, warmup=2, fail_at_step=7)
        with pytest.raises(RuntimeError, match="injected failure"):
            Trainer(lm, CFG, train_cfg=tc).fit(make_batch, ckpt_dir=d)
        assert latest_step(d) == 5
        tc2 = TrainConfig(steps=12, ckpt_every=5, warmup=2)
        p_resumed, _, _ = Trainer(lm, CFG, train_cfg=tc2).fit(make_batch, ckpt_dir=d)
    with tempfile.TemporaryDirectory() as d2:
        p_clean, _, _ = Trainer(lm, CFG, train_cfg=tc2).fit(make_batch, ckpt_dir=d2)
    for a, b in zip(jax.tree.leaves(p_resumed), jax.tree.leaves(p_clean)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_atomicity():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": jnp.arange(10), "b": {"c": jnp.ones((3, 3))}}
        for s in [10, 20, 30, 40]:
            save(d, s, tree, keep=2)
        assert latest_step(d) == 40
        restored, step = restore_latest(d, tree)
        assert step == 40
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(10))
        import os

        kept = [x for x in os.listdir(d) if x.startswith("step_")]
        assert len(kept) == 2  # gc keeps trailing 2


def test_trainer_with_mesh_and_accum():
    mesh = single_device_mesh()
    tc = TrainConfig(steps=4, warmup=1, accum=2, log_every=1)
    tr = Trainer(lm, CFG, mesh=mesh, rules=LM_RULES, train_cfg=tc)
    params, opt, hist = tr.fit(make_batch, steps=4)
    assert np.isfinite(hist[-1]["loss"])


def test_accum_matches_full_batch():
    """2-microbatch accumulation == full-batch gradients (same update)."""
    tc1 = TrainConfig(steps=1, warmup=1, accum=1)
    tc2 = TrainConfig(steps=1, warmup=1, accum=2)
    p1, _, _ = Trainer(lm, CFG, train_cfg=tc1).fit(make_batch, steps=1)
    p2, _, _ = Trainer(lm, CFG, train_cfg=tc2).fit(make_batch, steps=1)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_gradient_compression_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 64)) * 3.0
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    y = dequantize_int8(q, s)
    # max quantization error = scale/2
    assert float(jnp.max(jnp.abs(y - x))) <= float(s) * 0.51 + 1e-6


def test_compressed_psum_multidevice():
    """int8-compressed mean over a fake 4-device axis."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, use_mesh
from repro.optim import compressed_psum
mesh = make_mesh((4,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
f = jax.shard_map(lambda a: compressed_psum(a[0], "pod")[None],
              mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))
with use_mesh(mesh):
    got = f(x)
want = jnp.mean(x, axis=0)
err = float(jnp.max(jnp.abs(got - want[None])))
scale = float(jnp.max(jnp.abs(x)))/127.0
assert err <= scale * 1.01, (err, scale)
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_elastic_carve():
    from repro.distributed.elastic import carve_mesh

    mesh = carve_mesh(1)
    assert mesh.devices.size == 1


def test_axis_rules_resolution():
    mesh = single_device_mesh()  # axes: data, model
    with axis_rules(LM_RULES, mesh):
        spec = logical_to_spec("batch", "seq", "act_embed")
        # 'pod' is not in this mesh → dropped from the batch axes
        assert spec == jax.sharding.PartitionSpec(("data",), None, None)
        tree = spec_tree({"w": ("embed", "ff")})
        assert tree["w"] == jax.sharding.PartitionSpec("data", "model")
    # rules inactive → replicated
    spec = logical_to_spec("batch")
    assert spec == jax.sharding.PartitionSpec(None)
