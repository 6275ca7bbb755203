"""Mixed-traffic serving demo: LM continuous batching + vision frames
through the multi-engine front door (CPU).

The ServeEngine's slot lifecycle is unchanged — requests share decode
slots, join as slots free up, and outputs match per-request greedy
decode exactly (tests/test_serving.py) — but submission now goes through
the FrontDoor (repro.launch.serve), which routes each request to its
engine by type and merges the completion streams.  LM prefill runs the
chunked fast path (--prefill-chunk tokens per tick in one compiled
launch).

Run:  PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-3b
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.data import SyntheticVWW
from repro.launch.serve import FrontDoor
from repro.models.families import get_family
from repro.models.mobilenetv2 import MNV2Config, init_mnv2
from repro.serving import Request, ServeEngine, VisionEngine, VisionRequest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    help="text-family arch id (reduced config)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--vision-requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch).replace(dtype=jnp.float32)
    family = get_family(cfg)
    params, _ = family.init(jax.random.PRNGKey(0), cfg)
    lm = ServeEngine(params, cfg, max_batch=args.slots, max_len=256,
                     prefill_chunk=args.prefill_chunk)

    vcfg = MNV2Config(variant="p2m", image_size=40, width=0.25,
                      head_channels=64)
    vparams, vbn = init_mnv2(jax.random.PRNGKey(1), vcfg)
    vision = VisionEngine(vparams, vbn, vcfg, max_batch=4)

    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, int(rng.integers(4, 16))).tolist()
        reqs.append(Request(uid=uid, prompt=prompt,
                            max_new_tokens=args.new_tokens))
    frames = SyntheticVWW(image_size=40,
                          batch=args.vision_requests).batch_at(0)["images"]
    for uid in range(args.vision_requests):
        reqs.append(VisionRequest(uid=uid, image=frames[uid],
                                  arrival_tick=2 * uid))  # trickle of frames

    door = FrontDoor(lm=lm, vision=vision)
    t0 = time.perf_counter()
    done = door.run(reqs)
    dt = time.perf_counter() - t0

    lm_done = [r for n, r in done if n == "lm"]
    v_done = [r for n, r in done if n == "vision"]
    toks = sum(len(r.output) for r in lm_done)
    print(f"{args.arch} + p2m-vww via front door: {len(lm_done)} LM requests "
          f"/ {toks} tokens + {len(v_done)} frames in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s on CPU, {args.slots} slots, "
          f"prefill chunk {args.prefill_chunk})")
    for r in lm_done[:3]:
        print(f"  lm  req {r.uid}: prompt len {len(r.prompt)} "
              f"(prefill+decode {r.serve_ticks} ticks) → {r.output[:10]}…")
    for r in v_done[:3]:
        print(f"  img req {r.uid}: served@{r.served_tick} "
              f"queue={r.queue_ticks} ticks label={r.label}")
    for name, s in door.latency_summary().items():
        print(f"  {name}: launches={s['launches']} "
              f"mean_queue={s['mean_queue_ticks']:.2f} ticks "
              f"mean_launch={s['mean_launch_us'] / 1e3:.1f} ms")


if __name__ == "__main__":
    main()
