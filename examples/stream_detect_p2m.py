"""Streaming-video P²M detection demo (CPU): delta-gated multi-tick
streams through the StreamEngine, routed by the FrontDoor next to an LM
co-tenant and single-shot vision frames (DESIGN.md §9).

Each request is a whole synthetic moving-object stream occupying one
engine slot across ticks: per tick the deploy-folded P²M stem either
re-runs (frame delta crossed the gate threshold) or reuses the cached
activations of its reference frame; the CenterNet-lite head decodes
boxes and greedy-IoU association maintains per-stream tracks.  The
bandwidth numbers printed are *measured* — bits that actually crossed
the sensor boundary under event-style readout — next to the paper's
closed-form dense figure.

With --mesh, the stream microbatch (images, cached stems, rerun mask)
shards over the data mesh built from all visible devices.

Run:  PYTHONPATH=src python examples/stream_detect_p2m.py --streams 6
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.core.bandwidth import bandwidth_reduction
from repro.data import SyntheticVWW
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import FrontDoor
from repro.models.families import get_family
from repro.models.mobilenetv2 import (MNV2Config, head_out_channels,
                                      init_mnv2)
from repro.serving import Request, ServeEngine, VisionEngine, VisionRequest
from repro.video import (
    DeltaGateConfig,
    DetectConfig,
    StreamEngine,
    StreamRequest,
    SyntheticVideo,
    init_detect_head,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=6)
    ap.add_argument("--frames", type=int, default=12)
    ap.add_argument("--image-size", type=int, default=40)
    ap.add_argument("--max-streams", type=int, default=2)
    ap.add_argument("--threshold", type=float, default=0.0,
                    help="delta-gate threshold (mean |d| pixels; 0 = "
                         "lossless event gating)")
    ap.add_argument("--hold", type=int, default=2,
                    help="object positions advance every HOLD frames")
    ap.add_argument("--lm-requests", type=int, default=2)
    ap.add_argument("--vision-requests", type=int, default=4)
    ap.add_argument("--mesh", action="store_true",
                    help="shard the stream microbatch over all devices")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = MNV2Config(variant="p2m", image_size=args.image_size, width=0.25,
                     head_channels=64)
    params, bn = init_mnv2(jax.random.PRNGKey(0), cfg)
    # low score threshold: the head is untrained (like the serving demo's
    # "accuracy vs labels" line) — the point is the streaming machinery
    dcfg = DetectConfig(score_thresh=0.08)
    det = init_detect_head(
        jax.random.PRNGKey(1),
        head_out_channels(cfg), dcfg)
    mesh = make_debug_mesh() if args.mesh else None

    stream_engine = StreamEngine(
        params, bn, cfg, det, det_cfg=dcfg,
        gate=DeltaGateConfig(threshold=args.threshold),
        max_streams=args.max_streams, mesh=mesh)
    vision_engine = VisionEngine(params, bn, cfg, max_batch=4)

    lm_cfg = get_smoke_config("llama3.2-1b").replace(dtype=jnp.float32)
    lm_params, _ = get_family(lm_cfg).init(jax.random.PRNGKey(2), lm_cfg)
    lm = ServeEngine(lm_params, lm_cfg, max_batch=2, max_len=64,
                     prefill_chunk=4)

    rng = np.random.default_rng(0)
    reqs = []
    videos = {}
    for uid in range(args.streams):
        vid = SyntheticVideo(image_size=args.image_size,
                             n_frames=args.frames, seed=uid, hold=args.hold)
        videos[uid] = vid
        reqs.append(StreamRequest(uid=uid, frames=vid.frames(),
                                  gt_boxes=vid.gt_boxes(),
                                  arrival_tick=uid // 2))
    frames1 = SyntheticVWW(image_size=args.image_size,
                           batch=max(args.vision_requests, 1)).batch_at(0)
    for uid in range(args.vision_requests):
        reqs.append(VisionRequest(uid=1000 + uid,
                                  image=frames1["images"][uid],
                                  arrival_tick=uid))
    for uid in range(args.lm_requests):
        prompt = rng.integers(0, lm_cfg.vocab, 6).tolist()
        reqs.append(Request(uid=2000 + uid, prompt=prompt, max_new_tokens=8,
                            arrival_tick=2 * uid))

    door = FrontDoor(stream=stream_engine, vision=vision_engine, lm=lm)
    merged = door.run(reqs)
    streams = [r for n, r in merged if n == "stream"]

    dev = f"{len(mesh.devices.flat)}-device mesh" if mesh else "single device"
    print(f"front door served {len(streams)} video streams + "
          f"{len([1 for n, _ in merged if n == 'vision'])} frames + "
          f"{len([1 for n, _ in merged if n == 'lm'])} LM requests "
          f"on {dev} in {door.tick} front-door ticks\n")
    for r in streams:
        n_tracks = len({tid for frame in r.tracks for tid, _, _ in frame})
        print(f"  stream {r.uid}: {r.frames_done} frames over "
              f"{r.serve_ticks} ticks (queued {r.queue_ticks}), "
              f"stem-skip {r.skip_rate:.2f}, "
              f"{r.bits_per_frame:.0f} bits/frame vs "
              f"{r.dense_frame_bits} dense "
              f"({r.reduction_vs_dense:.2f}x measured), "
              f"{n_tracks} tracks (untrained head), "
              f"frame latency {r.frame_latency_us / 1e3:.1f} ms")
    s = stream_engine.stream_summary()
    print(f"\naggregate: stem-skip {s['stem_skip_rate']:.2f}, "
          f"{s['bits_per_frame']:.0f} bits/frame "
          f"({s['measured_reduction_vs_dense']:.2f}x measured reduction "
          f"vs dense readout)")
    print(f"paper Eq. 2 closed form (this geometry, dense single frame): "
          f"{bandwidth_reduction(stream_engine.geom):.2f}x vs raw sensor")


if __name__ == "__main__":
    main()
