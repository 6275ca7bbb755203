"""End-to-end driver (paper §5): train baseline and P²M-custom
MobileNetV2 on the synthetic VWW proxy, evaluate, then post-training
quantize the in-pixel layer and sweep output bit-precision (Fig. 7a).

Reduced geometry (80² images, width 0.25) so a few hundred steps run in
minutes on CPU; the model/geometry scale to the paper's 560² via flags.

Run:  PYTHONPATH=src python examples/train_vww_p2m.py --steps 300
      PYTHONPATH=src python examples/train_vww_p2m.py --steps 300 --sweep
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.core.bn_fold import deploy_params
from repro.launch.compile_cache import enable_compile_cache
from repro.core.quant import QuantSpec, quantize_deploy
from repro.data import SyntheticVWW
from repro.models.mobilenetv2 import MNV2Config, apply_mnv2, init_mnv2
from repro.optim import sgd, step_decay
from repro.train.vision import make_vww_eval, make_vww_train_step


def train(cfg, steps, lr, seed=0, log_every=50):
    ds = SyntheticVWW(image_size=cfg.image_size, batch=32, seed=seed)
    params, bn = init_mnv2(jax.random.PRNGKey(seed), cfg)
    # paper recipe: SGD momentum 0.9, step decay ×0.2
    opt = sgd(step_decay(lr, boundaries=(int(steps * 0.6), int(steps * 0.85))),
              momentum=0.9)
    state = {"params": params, "bn": bn, "opt": opt.init(params),
             "step": jnp.asarray(0, jnp.int32)}
    step_fn = jax.jit(make_vww_train_step(cfg, opt))
    for i in range(steps):
        state, m = step_fn(state, ds.batch_at(i))
        if log_every and (i + 1) % log_every == 0:
            print(f"  step {i+1}: loss={float(m['loss']):.4f} "
                  f"acc={float(m['acc']):.3f}")
    return state


def evaluate(cfg, state, n_batches=4, p2m_deploy=None):
    ev = make_vww_eval(cfg)
    accs = []
    for b in range(n_batches):
        batch = SyntheticVWW(image_size=cfg.image_size, batch=128,
                             seed=10_000 + b).batch_at(0)
        accs.append(ev(state["params"], state["bn"], batch,
                       p2m_deploy=p2m_deploy))
    return sum(accs) / len(accs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--image-size", type=int, default=80)
    ap.add_argument("--width", type=float, default=0.25)
    # the paper's 560² LRs are 0.03 / 0.003; the reduced 80² proxy needs a
    # hotter stem (stride-5 ⇒ 16² resolution) — defaults tuned for it
    ap.add_argument("--lr", type=float, default=0.03)
    ap.add_argument("--lr-p2m", type=float, default=0.05)
    ap.add_argument("--sweep", action="store_true",
                    help="Fig. 7a: output bit-precision sweep after training")
    args = ap.parse_args()
    enable_compile_cache()

    base_cfg = MNV2Config(variant="baseline", image_size=args.image_size,
                          width=args.width, head_channels=64)
    p2m_cfg = MNV2Config(variant="p2m", image_size=args.image_size,
                         width=args.width, head_channels=64)

    print("== baseline MobileNetV2 ==")
    base_state = train(base_cfg, args.steps, args.lr)
    base_acc = evaluate(base_cfg, base_state)
    print(f"baseline eval accuracy: {base_acc:.3f}")

    print("== P²M-custom MobileNetV2 (in-pixel first layer) ==")
    p2m_state = train(p2m_cfg, args.steps, args.lr_p2m)
    p2m_acc = evaluate(p2m_cfg, p2m_state)
    print(f"P²M eval accuracy: {p2m_acc:.3f} "
          f"(drop vs baseline: {base_acc - p2m_acc:+.3f}; paper: 1.47% at 560²)")

    # fold + deploy (what the manufactured sensor computes)
    dep = deploy_params(p2m_state["params"]["stem"], p2m_state["bn"]["stem"],
                        p2m_cfg.p2m)
    dep8 = quantize_deploy(dep, QuantSpec(w_bits=8, out_bits=8))
    dep_acc = evaluate(p2m_cfg, p2m_state, p2m_deploy=dep8)
    print(f"deployed (folded BN, 8-bit weights + 8-bit ADC): {dep_acc:.3f} "
          f"(paper: 8-bit PTQ is accuracy-neutral)")

    if args.sweep:
        print("== Fig. 7a sweep: ADC output bits ==")
        for bits in (16, 8, 6, 4):
            from repro.models.mobilenetv2 import MNV2Config as C
            from repro.core.p2m_conv import P2MConvConfig
            cfgq = MNV2Config(variant="p2m", image_size=args.image_size,
                              width=args.width, head_channels=64,
                              p2m=P2MConvConfig(n_bits=bits))
            depq = quantize_deploy(dep, QuantSpec(w_bits=8, out_bits=bits))
            acc = evaluate(cfgq, p2m_state, p2m_deploy=depq)
            print(f"  N_b={bits}: acc={acc:.3f}")


if __name__ == "__main__":
    main()
