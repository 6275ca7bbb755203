"""Training driver.

Runs an end-to-end training loop on the current host's devices (reduced
configs on CPU; the same code path scales to the production mesh — the
dry-run proves those shardings compile).  Wires: config → data pipeline
→ optimizer → jit'd train step (sharded when a mesh is available) →
Trainer (checkpointing, straggler monitor, restart).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 200 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
      --smoke --steps 50
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data import DataPipeline, SyntheticLMDataset
from repro.checkpoint import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.models.families import get_family
from repro.optim import adamw, cosine_warmup
from repro.parallel import plan_for, use_plan
from repro.parallel.sharding_utils import shardings_for
from repro.train import Trainer, TrainState, make_train_step
from repro.train.state import state_logical_axes
from repro.launch.mesh import make_debug_mesh


def build_batch_transform(cfg, batch_size, seq):
    """Attach stub modality inputs for vlm/encdec families."""
    def transform(batch):
        if cfg.family == "vlm":
            rng = np.random.default_rng(0)
            batch["image_embeds"] = rng.normal(
                0, 1, (batch_size, cfg.n_image_tokens, cfg.d_model)
            ).astype(np.float32)
        if cfg.family == "encdec":
            rng = np.random.default_rng(0)
            batch["src_embeds"] = rng.normal(
                0, 1, (batch_size, seq, cfg.d_model)).astype(np.float32)
        return batch
    return transform


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", choices=["int8_ef"], default=None)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(dtype=jnp.float32)  # CPU-friendly
    family = get_family(cfg)

    mesh = make_debug_mesh(model=args.model_parallel)
    plan = plan_for(mesh)

    dataset = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq,
                                 batch=args.batch)
    pipeline = DataPipeline(dataset,
                            transform=build_batch_transform(cfg, args.batch,
                                                            args.seq))

    optimizer = adamw(cosine_warmup(args.lr, warmup=20, total=args.steps))
    with use_plan(plan):
        params, param_axes = family.init(jax.random.PRNGKey(0), cfg)
        state = TrainState(params, optimizer.init(params))
        state_axes = state_logical_axes(param_axes, state["opt"])
        state_sh = shardings_for(state, state_axes, plan)
        step = make_train_step(cfg, optimizer, accum_steps=args.accum,
                               grad_compression=args.grad_compression)
        jitted = jax.jit(step, in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None), donate_argnums=(0,))

        def wrapped(state, batch):
            return jitted(state, batch)

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        trainer = Trainer(wrapped, state, pipeline, ckpt_manager=ckpt,
                          ckpt_every=args.ckpt_every if ckpt else 0)
        if ckpt is not None and trainer.restore():
            print(f"resumed from step {int(jax.device_get(trainer.state['step']))}")
        final = trainer.run(args.steps)
    pipeline.close()
    print(f"final: {final}")


if __name__ == "__main__":
    main()
