"""Subprocess worker: sharded training on 8 fake CPU devices.

Checks:
  1. pjit'd train step under a (2,4) ("data","model") mesh with full
     param/opt sharding specs + activation rules == single-device step.
  2. Checkpoint saved from the (2,4) mesh restores onto a (4,2) mesh
     (elastic reshard) and training continues bit-identically.
  3. compressed_psum (int8 wire format) approximates psum.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data import tokens as data_tokens  # noqa: E402
from repro.distributed import checkpoint as ckpt  # noqa: E402
from repro.distributed import sharding as shard  # noqa: E402
from repro.distributed.axisctx import default_rules, logical_axis_rules  # noqa: E402
from repro.distributed.grad_compression import compressed_psum  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.train import OptConfig, build_train_step, init_state  # noqa: E402
from repro.train import optimizer as opt_mod  # noqa: E402


def main():
    assert jax.device_count() == 8
    cfg = dataclasses.replace(
        get("qwen3_0_6b", reduced=True), param_dtype="float32",
        compute_dtype="float32", remat=False, d_model=128, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab=512)
    shape = ShapeConfig("t", 64, 8, "train")
    model = build(cfg)
    ocfg = OptConfig.for_arch(cfg, lr=1e-2, warmup_steps=2, total_steps=20)
    state = init_state(model, jax.random.PRNGKey(0), ocfg)
    batch = {k: jnp.asarray(v) for k, v in
             data_tokens.train_batch(cfg, shape, 0).items()}
    step_fn = build_train_step(model, ocfg)

    # single-device reference
    ref_state, ref_metrics = jax.jit(step_fn)(state, batch)
    ref_loss = float(ref_metrics["loss"])

    # sharded run on (2,4)
    mesh = make_host_mesh((2, 4), ("data", "model"))
    pspecs = shard.param_specs(cfg, mesh, state["params"])
    ospecs = opt_mod.state_specs(pspecs, state["params"], ocfg)
    sspec = {"params": pspecs, "opt": ospecs, "step": P()}
    from repro.models.zoo import input_specs  # late import
    bspecs = shard.batch_specs(cfg, mesh, shape,
                               {k: v for k, v in batch.items()})
    jstep = jax.jit(step_fn,
                    in_shardings=(shard.named(mesh, sspec),
                                  shard.named(mesh, bspecs)))
    with mesh, logical_axis_rules(mesh, default_rules(mesh)):
        sh_state, sh_metrics = jstep(state, batch)
        sh_loss = float(sh_metrics["loss"])
    assert abs(sh_loss - ref_loss) < 1e-4, (sh_loss, ref_loss)
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(sh_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print("SHARDED-STEP-OK", sh_loss)

    # elastic checkpoint: save from (2,4), restore on (4,2), keep training
    with tempfile.TemporaryDirectory() as td:
        ckpt.save_checkpoint(td, 1, sh_state, spec_tree=sspec)
        mesh2 = make_host_mesh((4, 2), ("data", "model"))
        pspecs2 = shard.param_specs(cfg, mesh2, state["params"])
        ospecs2 = opt_mod.state_specs(pspecs2, state["params"], ocfg)
        sspec2 = {"params": pspecs2, "opt": ospecs2, "step": P()}
        restored, _ = ckpt.restore_checkpoint(td, 1, sh_state, mesh=mesh2,
                                              spec_tree=sspec2)
        bspecs2 = shard.batch_specs(cfg, mesh2, shape, batch)
        jstep2 = jax.jit(step_fn,
                         in_shardings=(shard.named(mesh2, sspec2),
                                       shard.named(mesh2, bspecs2)))
        with mesh2, logical_axis_rules(mesh2, default_rules(mesh2)):
            st2, m2 = jstep2(restored, batch)
        # same step on the old mesh for comparison (the first jstep call's
        # outputs carry compiler-chosen shardings; re-lay them out to the
        # declared state spec before feeding them back in)
        sh_state_in = jax.device_put(sh_state, shard.named(mesh, sspec))
        with mesh, logical_axis_rules(mesh, default_rules(mesh)):
            st1, m1 = jstep(sh_state_in, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    print("ELASTIC-RESTORE-OK", float(m2["loss"]))

    # compressed psum
    mesh3 = make_host_mesh((8,), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 128)),
                    jnp.float32)

    def body(xs):
        return compressed_psum(xs, ("data",))

    out = jax.jit(jax.shard_map(body, mesh=mesh3, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False))(x)
    want = np.asarray(x).sum(axis=0)
    got = np.asarray(out)[0]
    scale = np.abs(np.asarray(x)).max() / 127
    assert np.abs(got - want).max() <= 8 * scale, \
        (np.abs(got - want).max(), scale)
    print("COMPRESSED-PSUM-OK")


if __name__ == "__main__":
    main()
