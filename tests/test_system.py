"""End-to-end behaviour: the paper's pipeline (P²M MobileNetV2 on
synthetic VWW) trains, beats chance, and deploys consistently; the LM
pipeline trains with falling loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bn_fold import deploy_params
from repro.core.quant import QuantSpec, quantize_deploy
from repro.data import DataPipeline, SyntheticLMDataset, SyntheticVWW
from repro.models.mobilenetv2 import MNV2Config, init_mnv2
from repro.optim import constant, sgd, adamw
from repro.train.vision import make_vww_eval, make_vww_train_step

P2M_SMOKE = MNV2Config(variant="p2m", image_size=40, width=0.25,
                       head_channels=32)
BASE_SMOKE = MNV2Config(variant="baseline", image_size=40, width=0.25,
                        head_channels=32)


def _train_vww(cfg, steps=40, seed=0):
    ds = SyntheticVWW(image_size=cfg.image_size, batch=32, seed=seed)
    params, bn = init_mnv2(jax.random.PRNGKey(seed), cfg)
    opt = sgd(constant(0.05), momentum=0.9)  # paper's optimizer
    state = {"params": params, "bn": bn, "opt": opt.init(params),
             "step": jnp.asarray(0, jnp.int32)}
    step = jax.jit(make_vww_train_step(cfg, opt))
    losses = []
    for i in range(steps):
        batch = ds.batch_at(i)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


def test_p2m_vww_trains_above_chance():
    state, losses = _train_vww(P2M_SMOKE, steps=80)
    assert np.mean(losses[-10:]) < np.mean(losses[:10])
    ds = SyntheticVWW(image_size=40, batch=128, seed=999)
    ev = make_vww_eval(P2M_SMOKE)
    acc = ev(state["params"], state["bn"], ds.batch_at(0))
    assert acc > 0.55, f"eval accuracy {acc} not above chance"


def test_p2m_deploy_consistency_after_training():
    """Fold + 8-bit quantization of the trained stem barely moves logits
    (the paper's PTQ claim: 8-bit ≈ fp)."""
    state, _ = _train_vww(P2M_SMOKE, steps=30)
    from repro.models.mobilenetv2 import apply_mnv2

    ds = SyntheticVWW(image_size=40, batch=16, seed=123)
    batch = ds.batch_at(0)
    logits_train, _ = apply_mnv2(state["params"], state["bn"], batch["images"],
                                 P2M_SMOKE, train=False)
    dep = deploy_params(state["params"]["stem"],
                        state["bn"]["stem"], P2M_SMOKE.p2m)
    dep8 = quantize_deploy(dep, QuantSpec(8, 8))
    logits_dep, _ = apply_mnv2(state["params"], state["bn"], batch["images"],
                               P2M_SMOKE, train=False, p2m_deploy=dep8)
    agree = (logits_train.argmax(-1) == logits_dep.argmax(-1)).mean()
    assert float(agree) > 0.85


def test_lm_training_loss_decreases():
    from repro.configs import get_smoke_config
    from repro.models.families import get_family
    from repro.train import TrainState, make_train_step

    cfg = get_smoke_config("llama3.2-1b").replace(dtype=jnp.float32)
    fam = get_family(cfg)
    params, _ = fam.init(jax.random.PRNGKey(0), cfg)
    opt = adamw(constant(3e-3), weight_decay=0.0)
    state = TrainState(params, opt.init(params))
    step = jax.jit(make_train_step(cfg, opt))
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=32, batch=8)
    losses = []
    for i in range(40):
        b = ds.batch_at(i)
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < 0.8 * np.mean(losses[:5])


def test_grad_accumulation_equivalence():
    """accum_steps=2 over a 2×batch equals two separate half-batches."""
    from repro.configs import get_smoke_config
    from repro.models.families import get_family
    from repro.train import TrainState, make_train_step

    cfg = get_smoke_config("llama3.2-1b").replace(dtype=jnp.float32)
    fam = get_family(cfg)
    params, _ = fam.init(jax.random.PRNGKey(0), cfg)
    opt = sgd(constant(1e-2), momentum=0.0)
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=16, batch=8)
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

    s1 = TrainState(params, opt.init(params))
    step1 = jax.jit(make_train_step(cfg, opt, accum_steps=1))
    out1, _ = step1(s1, batch)

    s2 = TrainState(params, opt.init(params))
    step2 = jax.jit(make_train_step(cfg, opt, accum_steps=2))
    out2, _ = step2(s2, batch)

    diff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                        out1["params"], out2["params"])
    assert max(jax.tree.leaves(diff)) < 1e-5


def test_compile_cache_dir_follows_env_else_fixed_repo_path(monkeypatch,
                                                            tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` wins and no code sets another
    directory; unset, the cache sits at a fixed, git-ignored path in the
    checkout."""
    from pathlib import Path

    from repro.launch.compile_cache import (
        REPO_CACHE_DIR,
        compile_cache_dir,
        enable_compile_cache,
    )

    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == str(REPO_CACHE_DIR)


@pytest.mark.parametrize("where", ["env", "repo"])
def test_compile_cache_entries_land_in_the_chosen_dir(tmp_path, where):
    """A process that enables the cache writes its compiled programs to
    `JAX_COMPILATION_CACHE_DIR` when it is set, else to the in-repo
    directory (pointed at ``tmp_path`` here, to keep the checkout clean)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=src, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    code = "import pathlib\nfrom repro.launch import compile_cache\n"
    if where == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    else:
        code += f"compile_cache.REPO_CACHE_DIR = pathlib.Path({str(tmp_path)!r})\n"
    code += ("import jax, jax.numpy as jnp\n"
             "compile_cache.enable_compile_cache()\n"
             "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(5))"
             ".block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())
