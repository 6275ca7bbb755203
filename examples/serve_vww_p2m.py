"""Batched vision serving demo on the P²M-MobileNetV2 (CPU), driven
through the multi-engine front door with an LM co-tenant.

Replays a bursty variable-arrival trace of synthetic VWW frames through
the VisionEngine — requests microbatch through the deploy-folded (BN
folded + 8-bit PTQ) P²M stem and backbone, free slots are zero-padded,
and per-request latency splits into queueing delay vs launch wall-clock
(DESIGN.md §7.2/§8) — while a handful of LM requests ride the same
FrontDoor, demonstrating mixed-modality routing and merged completion.

With --mesh, the vision microbatch is sharded over the data mesh built
from all visible devices (run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 to see 8-way DP on
CPU).  With --replicas N, the vision side becomes an N-replica
`ReplicaPool` behind least-loaded dispatch (DESIGN.md §11) — combined
with --mesh each replica gets its own disjoint submesh, i.e.
data-parallel *within* a replica, replica-parallel across the pool —
and --lm-tick-cost C makes the front door event-driven: the LM engine
fires once per C door ticks while vision fires every tick.

With --trace out.json, a deterministic tick-domain `Tracer` rides the
door (DESIGN.md §13) and the run exports a Chrome/Perfetto trace —
open it at ui.perfetto.dev to see every request's queue/serve spans
against the engine-tick tracks.  The run always ends with a metrics
registry snapshot: the counters, tick-histograms, and component views
every layer published during the replay.

Run:  PYTHONPATH=src python examples/serve_vww_p2m.py --requests 24
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_vww_p2m.py --requests 24 \
          --mesh --replicas 2 --lm-tick-cost 4 --trace door.json
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Tracer, default_registry
from repro.configs.p2m_vww import SERVE_MAX_BATCH, SERVE_MAX_QUEUE
from repro.data import SyntheticVWW
from repro.launch.mesh import make_debug_mesh, make_submeshes
from repro.launch.serve import FrontDoor
from repro.serving import (
    ReplicaPool,
    Request,
    ServeEngine,
    VisionEngine,
    VisionRequest,
)
from repro.models.families import get_family
from repro.models.mobilenetv2 import MNV2Config, init_mnv2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--lm-requests", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=80)
    ap.add_argument("--max-batch", type=int, default=SERVE_MAX_BATCH)
    ap.add_argument("--max-queue", type=int, default=SERVE_MAX_QUEUE)
    ap.add_argument("--mesh", action="store_true",
                    help="shard the vision microbatch over all devices")
    ap.add_argument("--replicas", type=int, default=1,
                    help="vision replicas in a least-loaded ReplicaPool "
                         "(with --mesh: one disjoint submesh per replica)")
    ap.add_argument("--lm-tick-cost", type=int, default=1,
                    help="front-door ticks per LM engine tick (>1 makes "
                         "the door event-driven, DESIGN.md §11)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="export a Perfetto tick-domain trace of the "
                         "replay to this path (DESIGN.md §13)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = MNV2Config(variant="p2m", image_size=args.image_size, width=0.25,
                     head_channels=64)
    params, bn = init_mnv2(jax.random.PRNGKey(0), cfg)
    batch = SyntheticVWW(image_size=args.image_size,
                         batch=args.requests).batch_at(0)

    if args.replicas > 1:
        meshes = (make_submeshes(args.replicas) if args.mesh
                  else [None] * args.replicas)
        engine = ReplicaPool(*(
            VisionEngine(params, bn, cfg, max_batch=args.max_batch,
                         max_queue=args.max_queue, mesh=m) for m in meshes))
    else:
        mesh = make_debug_mesh() if args.mesh else None
        engine = VisionEngine(params, bn, cfg, max_batch=args.max_batch,
                              max_queue=args.max_queue, mesh=mesh)

    # bursty arrivals: clumps of frames every few ticks
    rng = np.random.default_rng(0)
    tick, reqs = 0, []
    for uid in range(args.requests):
        if uid and uid % 5 == 0:
            tick += int(rng.integers(1, 4))
        reqs.append(VisionRequest(uid=uid, image=batch["images"][uid],
                                  arrival_tick=tick))

    # LM co-tenant: a few short prompts share the front door
    lm_cfg = get_smoke_config("llama3.2-1b").replace(dtype=jnp.float32)
    lm_fam = get_family(lm_cfg)
    lm_params, _ = lm_fam.init(jax.random.PRNGKey(1), lm_cfg)
    lm = ServeEngine(lm_params, lm_cfg, max_batch=2, max_len=64,
                     prefill_chunk=4, tick_cost=args.lm_tick_cost)
    for uid in range(args.lm_requests):
        prompt = rng.integers(0, lm_cfg.vocab, 6).tolist()
        reqs.append(Request(uid=1000 + uid, prompt=prompt, max_new_tokens=8,
                            arrival_tick=2 * uid))

    tracer = Tracer() if args.trace else None
    door = FrontDoor(tracer=tracer, vision=engine, lm=lm)
    merged = door.run(reqs)
    done = [r for n, r in merged if n == "vision"]
    lm_done = [r for n, r in merged if n == "lm"]

    correct = sum(r.label == int(batch["labels"][r.uid]) for r in done)
    n_dev = len(jax.devices()) if args.mesh else 1
    dev = (f"{args.replicas}x {n_dev // args.replicas}-device replicas"
           if args.replicas > 1 else
           f"{n_dev}-device mesh" if args.mesh else "single device")
    print(f"served {len(done)}/{args.requests} frames on {dev} "
          f"(accuracy vs labels {correct / len(done):.2f} — untrained net) "
          f"+ {len(lm_done)} LM requests")
    for r in done[: args.max_batch + 2]:
        print(f"  uid={r.uid:3d} arrived@{r.arrival_tick:<3d} "
              f"served@{r.served_tick:<3d} queue={r.queue_ticks} ticks  "
              f"launch={r.batch_wall_us / 1e3:.1f} ms  label={r.label}")
    s = engine.latency_summary()
    print(f"launches={s['launches']} utilization={s['utilization']:.2f} "
          f"mean_queue={s['mean_queue_ticks']:.2f} ticks "
          f"mean_launch={s['mean_launch_us'] / 1e3:.1f} ms "
          f"evictions={s['evictions']}")

    if tracer is not None:
        tracer.export(args.trace)
        print(f"trace: {len(tracer.trace_events())} events -> {args.trace} "
              "(open at ui.perfetto.dev)")
    snap = default_registry().snapshot()
    print("\nmetrics registry snapshot (DESIGN.md §13.2):")
    print(json.dumps(snap, indent=2, sort_keys=True, default=str))


if __name__ == "__main__":
    main()
