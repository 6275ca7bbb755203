"""Multi-device SPMD tests — run in a subprocess with 8 virtual CPU
devices (the main process keeps 1 device for every other test)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(script: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.families import get_family
        from repro.optim import sgd, constant
        from repro.train import TrainState, make_train_step
        from repro.train.state import state_logical_axes
        from repro.parallel import plan_for, use_plan
        from repro.parallel.sharding_utils import shardings_for
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("qwen3-32b").replace(dtype=jnp.float32)
        fam = get_family(cfg)
        batch = {
            "tokens": jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)), jnp.int32),
            "targets": jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab, (8, 16)), jnp.int32),
        }
        opt = sgd(constant(1e-2))
        step = make_train_step(cfg, opt)

        # single-device reference
        params, axes = fam.init(jax.random.PRNGKey(0), cfg)
        s0 = TrainState(params, opt.init(params))
        ref_state, ref_metrics = jax.jit(step)(s0, batch)

        # sharded: 4-way data x 2-way model
        mesh = make_debug_mesh(8, model=2)
        plan = plan_for(mesh, fsdp=True)
        with use_plan(plan):
            params2, axes2 = fam.init(jax.random.PRNGKey(0), cfg)
            s1 = TrainState(params2, opt.init(params2))
            st_axes = state_logical_axes(axes2, s1["opt"])
            sh = shardings_for(s1, st_axes, plan)
            jitted = jax.jit(step, in_shardings=(sh, None), out_shardings=(sh, None))
            out_state, metrics = jitted(s1, batch)

        diff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                            ref_state["params"], out_state["params"])
        max_diff = max(jax.tree.leaves(diff))
        n_shards = len(jax.tree.leaves(out_state["params"])[0].sharding.device_set)
        print(json.dumps({"loss_ref": float(ref_metrics["loss"]),
                          "loss_sharded": float(metrics["loss"]),
                          "max_param_diff": max_diff,
                          "devices": len(jax.devices())}))
    """))
    assert res["devices"] == 8
    assert abs(res["loss_ref"] - res["loss_sharded"]) < 1e-3
    assert res["max_param_diff"] < 1e-3


def test_sharded_moe_and_decode():
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.families import get_family
        from repro.parallel import plan_for, use_plan
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("qwen3-moe-30b-a3b").replace(dtype=jnp.float32)
        fam = get_family(cfg)
        params, _ = fam.init(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (4, 24)), jnp.int32)
        batch = {"tokens": toks, "targets": toks}
        loss_ref, _ = fam.loss(params, batch, cfg)

        mesh = make_debug_mesh(8, model=4)  # experts (8) over model=4
        plan = plan_for(mesh)
        with use_plan(plan), mesh:
            loss_sh, _ = jax.jit(lambda p, b: fam.loss(p, b, cfg))(params, batch)

        # sharded decode with sequence-sharded cache
        plan_d = plan_for(mesh, cache_seq_shard=True)
        with use_plan(plan_d), mesh:
            state, _ = fam.init_decode_state(cfg, 4, 64)
            lg, _ = jax.jit(lambda p, s, t, pos: fam.decode(p, s, t, pos, cfg))(
                params, state, toks[:, :1], jnp.zeros((4,), jnp.int32))
        print(json.dumps({"loss_ref": float(loss_ref), "loss_sh": float(loss_sh),
                          "decode_finite": bool(jnp.all(jnp.isfinite(lg)))}))
    """))
    assert abs(res["loss_ref"] - res["loss_sh"]) < 1e-3
    assert res["decode_finite"]


def test_sharded_vww_train_matches_single_device():
    """The paper's workload at scale: P²M-MobileNetV2 VWW train step,
    8-way data-parallel with int8_ef gradient compression, matches the
    single-device step within 1e-3 on loss, params, and BN state.

    The parity assertion is on ONE step from identical state.  Multi-step
    trajectories are *not* comparable at tight tolerance: the saturating
    P²M ReLU / relu6 clips make the gradient a discontinuous function of
    the pre-activation, so an O(float-reassociation) forward difference
    can flip a clip mask and amplify chaotically across steps (DESIGN.md
    §7).  The sharded run is continued a few more steps to assert the
    compressed DP step keeps training (finite losses, advancing step
    counter, EF state carried)."""
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.data import SyntheticVWW
        from repro.models.mobilenetv2 import MNV2Config, init_mnv2
        from repro.optim import sgd, constant
        from repro.train.vision import (make_vww_train_step, vww_train_state,
                                        vww_train_shardings)
        from repro.parallel import use_plan, vision_plan_for
        from repro.launch.mesh import make_debug_mesh

        cfg = MNV2Config(variant="p2m", image_size=40, width=0.25,
                         head_channels=32)
        ds = SyntheticVWW(image_size=40, batch=32, seed=0)
        opt = sgd(constant(0.01), momentum=0.9)
        step = make_vww_train_step(cfg, opt, grad_compression="int8_ef")

        # single-device reference: one step from state S0
        params, bn = init_mnv2(jax.random.PRNGKey(0), cfg)
        ref = vww_train_state(params, bn, opt.init(params),
                              grad_compression="int8_ef")
        ref1, mref = jax.jit(step)(ref, ds.batch_at(0))

        # 8-way data-parallel with the vision plan, same S0
        mesh = make_debug_mesh(8)
        plan = vision_plan_for(mesh)
        with use_plan(plan), mesh:
            st = vww_train_state(params, bn, opt.init(params),
                                 grad_compression="int8_ef")
            batch0 = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
            st_sh, b_sh = vww_train_shardings(st, batch0, plan)
            jsh = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None))
            st, msh = jsh(st, jax.device_put(batch0, b_sh))
            pdiff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                 ref1["params"], st["params"])
            bdiff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                 ref1["bn"], st["bn"])
            # keep the sharded run going: compressed DP training advances
            losses = [float(msh["loss"])]
            for i in range(1, 5):
                batch = jax.device_put(
                    {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()},
                    b_sh)
                st, m = jsh(st, batch)
                losses.append(float(m["loss"]))
            replicas = len(
                jax.tree.leaves(st["params"])[0].sharding.device_set)
        print(json.dumps({
            "loss_ref": float(mref["loss"]), "losses_sh": losses,
            "max_param_diff": max(jax.tree.leaves(pdiff)),
            "max_bn_diff": max(jax.tree.leaves(bdiff)),
            "has_ef": "extras" in st,
            "step_count": int(st["step"]),
            "param_replicas": replicas,
            "devices": len(jax.devices())}))
    """))
    assert res["devices"] == 8
    assert res["has_ef"]
    assert res["param_replicas"] == 8  # replicated param tree spans the mesh
    assert abs(res["loss_ref"] - res["losses_sh"][0]) < 1e-3
    assert res["max_param_diff"] < 1e-3
    assert res["max_bn_diff"] < 1e-3
    assert res["step_count"] == 5
    assert all(np.isfinite(l) for l in res["losses_sh"])


def test_grad_compression_under_sharding():
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.families import get_family
        from repro.optim import sgd, constant
        from repro.train import TrainState, make_train_step
        from repro.parallel import plan_for, use_plan
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("llama3.2-1b").replace(dtype=jnp.float32)
        fam = get_family(cfg)
        opt = sgd(constant(1e-2))
        step = make_train_step(cfg, opt, grad_compression="int8_ef")
        mesh = make_debug_mesh(8, model=2)
        plan = plan_for(mesh)
        rng = np.random.default_rng(0)
        with use_plan(plan), mesh:
            params, _ = fam.init(jax.random.PRNGKey(0), cfg)
            state = TrainState(params, opt.init(params))
            losses = []
            jstep = jax.jit(step)
            for i in range(8):
                toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)
                batch = {"tokens": toks, "targets": toks}
                state, m = jstep(state, batch)
                losses.append(float(m["loss"]))
        print(json.dumps({"first": losses[0], "last": losses[-1],
                          "has_ef": "extras" in state}))
    """))
    assert res["has_ef"]
    assert res["last"] < res["first"]  # training advances under compression


def test_pallas_conv_runs_per_batch_shard_under_a_plan():
    """XLA cannot partition a Mosaic kernel, so under a sharding plan the
    P²M conv kernel runs inside a shard_map over the batch axis: loss
    and gradients equal the unsharded ones, and the traced program holds
    the shard_map (on one device, outside a plan, it does not)."""
    res = _run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.core.p2m_conv import (P2MConvConfig, apply_p2m_conv_train,
                                         init_p2m_conv, init_p2m_state)
        from repro.launch.mesh import make_debug_mesh
        from repro.parallel import use_plan, vision_plan_for

        cfg = P2MConvConfig()
        params = init_p2m_conv(jax.random.PRNGKey(0), cfg)
        st = init_p2m_state(cfg)
        imgs = jnp.asarray(np.random.default_rng(0).random((8, 20, 20, 3)),
                           jnp.float32)

        def loss(p):
            y, _ = apply_p2m_conv_train(p, st, imgs, cfg, impl="pallas")
            return (y ** 2).sum()

        vg = jax.value_and_grad(loss)
        ref_l, ref_g = jax.jit(vg)(params)
        plain = "shard_map" in str(jax.make_jaxpr(vg)(params))
        mesh = make_debug_mesh(8)
        with use_plan(vision_plan_for(mesh)), mesh:
            mapped = "shard_map" in str(jax.make_jaxpr(vg)(params))
            l, g = jax.jit(vg)(params)
        gdiff = max(float(jnp.abs(a - b).max())
                    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(g)))
        print(json.dumps({"plain": plain, "mapped": mapped,
                          "dl": abs(float(l) - float(ref_l)),
                          "gdiff": gdiff, "scale": float(ref_l)}))
    """))
    assert not res["plain"] and res["mapped"]
    assert res["dl"] <= 1e-5 * res["scale"]
    assert res["gdiff"] < 1e-4
