"""Sharding-plan machinery: spec sanitization, rule tables, spec trees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.specs import input_specs, plan_for_cell
from repro.parallel import plan_for, sanitize_spec, shard, use_plan
from repro.parallel.axes import logical_spec
from repro.parallel.sharding_utils import shardings_for


def _mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def _mesh16():
    """Abstract 16-device mesh shape for sanitization tests (no devices
    touched — sanitize only reads mesh.shape)."""
    class FakeMesh:
        shape = {"data": 4, "model": 4}
    return FakeMesh()


def test_sanitize_divisibility():
    m = _mesh16()
    spec = sanitize_spec((8, 12), P("data", "model"), m)
    assert spec == P("data", "model")
    spec = sanitize_spec((6, 12), P("data", "model"), m)  # 6 % 4 != 0
    assert spec == P(None, "model")


def test_sanitize_missing_axis():
    m = _mesh16()
    spec = sanitize_spec((8, 8), P(("pod", "data"), None), m)
    assert spec == P("data", None)


def test_sanitize_duplicate_axis_conflict():
    """MoE fallback: expert takes 'model'; mlp dim loses the conflict."""
    m = _mesh16()
    spec = sanitize_spec((8, 16, 16), P("model", None, "model"), m)
    assert spec == P("model", None, None)
    # when the first dim is not divisible, the later dim inherits the axis
    spec = sanitize_spec((6, 16, 16), P("model", None, "model"), m)
    assert spec == P(None, None, "model")


def test_logical_spec_resolution():
    mesh = _mesh()
    plan = plan_for(mesh)
    spec = logical_spec((4, 8), ("batch", "seq"), plan)
    # pod axis absent on single-pod mesh → dropped
    assert spec == P("data", None)


def test_shard_noop_outside_plan():
    x = jnp.ones((4, 4))
    y = shard(x, "batch", "seq")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fsdp_plan_shards_embed():
    plan = plan_for(_mesh(), fsdp=True)
    assert plan.rules["embed"] == "data"
    plan2 = plan_for(_mesh(), fsdp=False)
    assert plan2.rules["embed"] is None


@pytest.mark.parametrize("arch", ["qwen3-32b", "mixtral-8x22b", "whisper-tiny",
                                  "llama-3.2-vision-11b"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_input_specs_shapes(arch, shape):
    cfg = get_config(arch)
    spec = SHAPES[shape]
    inputs, axes = input_specs(cfg, spec)
    assert set(inputs) == set(axes)
    if spec.kind == "train":
        assert inputs["tokens"].shape[0] == spec.global_batch
        if cfg.family == "encdec":
            assert inputs["src_embeds"].shape[1] == spec.seq_len
            assert inputs["tokens"].shape[1] == 448
        else:
            assert inputs["tokens"].shape[1] == spec.seq_len
    else:
        assert inputs["tokens"].shape == (spec.global_batch, 1)


def test_shardings_tree_structure():
    mesh = _mesh()
    plan = plan_for(mesh)
    values = {"a": jnp.zeros((4, 8)), "b": {"c": jnp.zeros((2,))}}
    axes = {"a": ("batch", "embed"), "b": {"c": ("heads",)}}
    sh = shardings_for(values, axes, plan)
    assert sh["a"].spec == P("data", None)
    assert sh["b"]["c"].spec == P("model") or sh["b"]["c"].spec == P(None)


def test_plan_for_cell_decode_uses_cache_sharding():
    cfg = get_config("qwen3-32b")
    mesh = _mesh()
    plan = plan_for_cell(cfg, SHAPES["decode_32k"], mesh)
    assert plan.rules["cache_seq"] == "model"
    plan_b1 = plan_for_cell(cfg, SHAPES["long_500k"], mesh)
    assert plan_b1.rules["cache_seq"] == ("data", "model")
    plan_train = plan_for_cell(cfg, SHAPES["train_4k"], mesh)
    assert plan_train.rules["cache_seq"] is None
    assert plan_train.rules["embed"] == "data"  # 32B model → FSDP


# ------------------------------------------------------------- vision DP


def test_vision_plan_is_pure_data_parallel():
    from repro.parallel import vision_plan_for

    plan = vision_plan_for(_mesh())
    spec = logical_spec((32, 40, 40, 3), ("batch", None, None, None), plan)
    assert spec == P("data", None, None, None)
    used = set()
    for v in plan.rules.values():
        if v is not None:
            used.update((v,) if isinstance(v, str) else v)
    assert "model" not in used  # the model axis stays free for LM co-tenants


def test_replicated_tree_and_batch_shardings():
    from repro.launch.mesh import make_debug_mesh
    from repro.parallel import vision_plan_for
    from repro.parallel.sharding_utils import batch_shardings, replicated_tree

    mesh = make_debug_mesh()
    plan = vision_plan_for(mesh)
    state = {"params": {"w": jnp.ones((4, 3))}, "step": jnp.zeros((), jnp.int32)}
    rep = replicated_tree(state, plan)
    assert all(s.spec == P() for s in jax.tree.leaves(rep))

    batch = {"images": jnp.ones((8, 6, 6, 3)), "labels": jnp.ones((8,), jnp.int32),
             "mixup_lam": jnp.float32(0.2)}
    bs = batch_shardings(batch, plan)
    assert bs["images"].spec == P("data", None, None, None)
    assert bs["labels"].spec == P("data")
    assert bs["mixup_lam"].spec == P()  # scalar leaves replicate
    placed = jax.device_put(batch, bs)
    np.testing.assert_array_equal(np.asarray(placed["images"]),
                                  np.asarray(batch["images"]))


# ----------------------------- multi-device lane (scripts/ci.sh runs this
# file again under XLA_FLAGS=--xla_force_host_platform_device_count=8)

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 virtual devices (CI multi-device lane)")


@needs8
def test_batch_shardings_distribute_eight_ways():
    from repro.launch.mesh import make_debug_mesh
    from repro.parallel import vision_plan_for
    from repro.parallel.sharding_utils import batch_shardings

    mesh = make_debug_mesh(8)
    plan = vision_plan_for(mesh)
    batch = {"x": jnp.arange(32.0).reshape(32, 1)}
    placed = jax.device_put(batch, batch_shardings(batch, plan))
    assert len(placed["x"].sharding.device_set) == 8
    with use_plan(plan), mesh:
        m = jax.jit(lambda b: shard(b["x"], "batch", None).mean())(placed)
    assert float(m) == 15.5  # global (cross-device) reduction


@needs8
def test_shard_constraint_partitions_jitted_compute():
    from repro.launch.mesh import make_debug_mesh
    from repro.parallel import vision_plan_for

    mesh = make_debug_mesh(8)
    plan = vision_plan_for(mesh)
    x = jnp.arange(64.0).reshape(16, 4)
    with use_plan(plan), mesh:
        y = jax.jit(lambda v: shard(v, "batch", None) * 2.0)(x)
    assert len(y.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2.0)


def test_debug_mesh_axes_are_auto_so_plans_can_constrain():
    """`jax.make_mesh` builds Explicit axes by default; the plans place
    arrays with `with_sharding_constraint`, which needs Auto ones — so
    every mesh the launch helpers build is Auto, and a constrained jit
    under ``with use_plan(plan), mesh:`` lowers."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh()
    assert set(mesh.axis_types) == {AxisType.Auto}
    plan = plan_for(mesh)
    with use_plan(plan), mesh:
        y = jax.jit(lambda x: shard(x, "batch", None) * 2)(jnp.ones((4, 2)))
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 2)))


def test_chip_peaks_keyed_by_device_kind():
    """Published v5e peaks, keyed by `device_kind`; an unknown kind is an
    error, never a default."""
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert chip_peaks(PRODUCTION_DEVICE_KIND) is v5e
    assert (v5e.flops_bf16, v5e.ops_int8) == (197e12, 393e12)
    assert v5e.hbm_bytes_per_s == 819e9
    assert v5e.ici_bytes_per_s * 8 == 1600e9  # 1,600 Gbit/s per chip
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks(jax.devices()[0].device_kind + " (unknown)")
