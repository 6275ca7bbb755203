"""StreamEngine: multi-tick streaming-video detection over the shared
scheduler core (DESIGN.md §9).

A request here is a whole video **stream**: it occupies one slot of the
scheduler's fixed table for as many ticks as it has frames, advancing
one frame per engine tick — the first workload to use the multi-tick
slot lifetime the core was built for with *vision* compute in the slot
(the LM engine holds slots for many ticks; `VisionEngine` holds them
for exactly one).

Per-slot state the core's admit/recycle contract manages via
``_on_admit`` (the isolation invariant `tests/test_scheduler.py` pins):

* a `DeltaGate` — reference frame + measured-bandwidth ledger;
* cached stem activations — the P²M output of the reference frame;
* a `Tracker` — live tracks and the per-stream id counter.

Every tick is ONE compiled, shape-stable launch over the whole slot
table: the deploy-folded P²M stem runs on the padded image batch, a
per-slot ``rerun`` mask selects fresh stem activations or the cached
ones, and the backbone + CenterNet-lite heads + top-k decode ride the
same launch.  The stem select has two paths (``stem_path``):

* ``"where"`` — the reference: compute the stem for every slot, then a
  host-visible `jnp.where` discards skipped results.  Shape-stable, but
  every masked-off slot still pays the full stem FLOPs.
* ``"gated"`` — the fused kernel (`kernels/p2m_conv/gated.py`,
  DESIGN.md §3.6): the rerun mask and the cached stem ride INTO the
  Pallas kernel as operands and masked-off tiles short-circuit to a
  cache copy — one launch, no wasted stem FLOPs, no host round-trip.
  Bitwise-identical to the where-select by construction (bench-gated at
  1.0).  ``"auto"`` picks it on a TPU single-device engine and falls
  back to ``"where"`` elsewhere (interpret-mode gating would *measure*
  the Python interpreter; a mesh needs the where path's sharded XLA
  select).

Either way the thing the delta gate models is the **sensor readout**: a
skipped tick transmits no activation map, and the bits ledger measures
exactly that.  With ``threshold=0`` the gate only skips bit-identical
frames, so gated detections equal the dense engine's exactly (pinned by
test).

Scale-out mirrors `VisionEngine`: pass ``mesh=`` and the image batch,
cached-stem batch, and rerun mask shard over the data axes of the §7.1
vision plan while params/deploy/head trees replicate.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.p2m_vww import (
    SERVE_QUANT_BITS,
    STREAM_MAX_QUEUE,
    STREAM_MAX_SLOTS,
)
from repro.core.bandwidth import (
    FirstLayerGeom,
    StreamBandwidthLedger,
    frame_output_bits,
)
from repro.core.bn_fold import deploy_params
from repro.core.quant import QuantSpec, quantize_deploy
from repro.models.mobilenetv2 import (
    MNV2Config,
    apply_mnv2_backbone,
    apply_mnv2_stem,
)
from repro.obs.metrics import counted_lru_cache
from repro.parallel import under_plan, vision_plan_for
from repro.parallel.sharding_utils import batch_shardings
from repro.serving.scheduler import ScheduledRequest, SlotEngine
from repro.video.delta import DeltaGate, DeltaGateConfig
from repro.video.detect import (
    DetectConfig,
    apply_detect_head,
    decode_detections,
    det_grid,
)
from repro.video.track import Tracker


@dataclasses.dataclass
class StreamRequest(ScheduledRequest):
    """One video stream = one multi-tick slot occupancy.

    Bandwidth numbers all read through ``ledger`` — the stream's
    `StreamBandwidthLedger`, owned by its slot's `DeltaGate` and
    attached on admit — so there is exactly one copy of the readout
    accounting (`core/bandwidth.py` defines the formulas)."""

    uid: int
    frames: np.ndarray  # (T, H, W, 3) float32 in [0, 1]
    gt_boxes: np.ndarray | None = None  # optional (T, N, 4) ground truth

    # Filled by the engine, one entry per served frame:
    frame_outputs: list = dataclasses.field(default_factory=list)  # (boxes, scores)
    tracks: list = dataclasses.field(default_factory=list)  # [(tid, box, score)]
    frames_done: int = 0
    ledger: StreamBandwidthLedger | None = None  # attached on admit

    @property
    def n_frames(self) -> int:
        return len(self.frames)

    @property
    def skip_count(self) -> int:
        """Frames that reused the cached stem (transmitted nothing)."""
        return self.ledger.frames - self.ledger.rerun_frames if self.ledger else 0

    @property
    def bits(self) -> int:
        """Measured transmitted bits over the stream so far."""
        return self.ledger.bits if self.ledger else 0

    @property
    def skip_rate(self) -> float:
        return self.ledger.skip_rate if self.ledger else 0.0

    @property
    def bits_per_frame(self) -> float:
        return self.ledger.bits_per_frame if self.ledger else 0.0

    @property
    def dense_frame_bits(self) -> int:
        return self.ledger.dense_bits_per_frame if self.ledger else 0

    @property
    def reduction_vs_dense(self) -> float:
        """Measured bandwidth reduction vs re-transmitting every frame."""
        return self.ledger.reduction_vs_dense if self.ledger else 0.0

    @property
    def frame_latency_us(self) -> float:
        """Mean per-frame launch wall-clock over the stream so far."""
        return self.launch_wall_us / self.frames_done if self.frames_done else 0.0


@counted_lru_cache("stream_forward")
def _stream_forward_for(cfg: MNV2Config, dcfg: DetectConfig,
                        mesh: Mesh | None, batch: int,
                        impl: str | None = None,
                        stem_path: str = "where",
                        interpret: bool | None = None):
    """One compiled launch: gated stem → backbone → heads → top-k decode.

    Params, BN, deploy, and detection-head trees ride as traced
    arguments so every engine on this (cfg, dcfg, mesh, batch, impl,
    stem_path) shares one compilation; under a mesh the batched operands
    shard over the data axes (§7.1 plan) and everything else replicates.
    ``impl`` selects the stem conv path on the ``"where"`` select —
    the degradation ladder requests ``"patches"`` after repeated kernel
    faults (DESIGN.md §10); ``stem_path="gated"`` instead runs the
    fused delta-gated Pallas stem (cache + mask in-kernel, §3.6) and
    requires ``mesh=None``.

    The cached stem is *validated on device*: a slot whose cache holds
    any non-finite value (a corrupted analog activation that slipped
    into state, arXiv:2304.02968's fault class) is forced to re-run, and
    the **effective** rerun mask returns to the host so the ledger
    meters what actually happened and the engine can drop that slot's
    gate to dense.  When every cache row is finite the effective mask
    equals the requested one, so the guard is bitwise-free in the
    fault-free path.
    """
    if stem_path not in ("where", "gated"):
        raise ValueError(f"unknown stem_path {stem_path!r}")
    if stem_path == "gated" and mesh is not None:
        raise ValueError("stem_path='gated' needs mesh=None: the fused "
                         "kernel takes the whole slot table in one launch; "
                         "sharded engines keep the where-select")

    grid = det_grid(cfg.p2m.out_spatial(cfg.image_size))
    if stem_path == "gated":
        from repro.core.pixel_model import default_pixel_model
        from repro.kernels.p2m_conv.gated import p2m_conv_pallas_gated
        from repro.kernels.p2m_conv.ops import _coeff_tuple

        gated_coeffs = _coeff_tuple(default_pixel_model())
        gated_interpret = (jax.default_backend() != "tpu"
                           if interpret is None else interpret)

    def forward(params, bn, dep, det, images, cached, rerun):
        cache_ok = jnp.isfinite(cached).all(axis=(1, 2, 3))
        rerun = rerun | ~cache_ok
        if stem_path == "gated":
            # deploy-form stem (conv → quantizing ADC epilogue, matching
            # apply_p2m_conv_deploy) with the select fused in-kernel
            stem = p2m_conv_pallas_gated(
                images, dep["w"], dep["shift"], cached, rerun,
                kernel=cfg.p2m.kernel, stride=cfg.p2m.stride,
                coeffs=gated_coeffs, mode="quant",
                v_lsb=cfg.p2m.adc.v_lsb, max_count=cfg.p2m.adc.max_count,
                interpret=gated_interpret)
        else:
            stem, _ = apply_mnv2_stem(params, bn, images, cfg, None,
                                      train=False, p2m_deploy=dep,
                                      p2m_impl=impl)
            stem = jnp.where(rerun[:, None, None, None], stem, cached)
        feats, _ = apply_mnv2_backbone(params, bn, stem, cfg, train=False)
        boxes, scores = decode_detections(
            apply_detect_head(det, feats, grid), dcfg.max_dets)
        return stem, boxes, scores, rerun

    if mesh is None:
        return jax.jit(forward)
    plan = vision_plan_for(mesh)
    h = w = cfg.image_size
    ho = cfg.p2m.out_spatial(h)
    wo = cfg.p2m.out_spatial(w)
    co = cfg.p2m.out_channels
    img = batch_shardings(
        jax.ShapeDtypeStruct((batch, h, w, 3), jnp.float32), plan)
    cach = batch_shardings(
        jax.ShapeDtypeStruct((batch, ho, wo, co), jnp.float32), plan)
    msk = batch_shardings(jax.ShapeDtypeStruct((batch,), jnp.bool_), plan)
    rep = NamedSharding(mesh, P())
    # the plan runs the stem kernel per batch shard; the stem comes back
    # *sharded* (it feeds straight into next tick's cached-stem operand,
    # same sharding — no per-tick gather/reshard); the decoded
    # boxes/scores and effective rerun mask replicate to the host
    return jax.jit(under_plan(forward, plan),
                   in_shardings=(rep, rep, rep, rep, img, cach, msk),
                   out_shardings=(cach, rep, rep, rep))


class StreamEngine(SlotEngine):
    """Multi-tick streaming detection engine; see module docstring."""

    request_type = StreamRequest

    def __init__(self, params, bn_state, cfg: MNV2Config, det_params, *,
                 det_cfg: DetectConfig = DetectConfig(),
                 gate: DeltaGateConfig = DeltaGateConfig(),
                 max_streams: int = STREAM_MAX_SLOTS,
                 max_queue: int | None = STREAM_MAX_QUEUE,
                 deploy_quant_bits: int | None = SERVE_QUANT_BITS,
                 iou_thresh: float = 0.3,
                 mesh: Mesh | None = None,
                 evict: str = "drop-newest",
                 degrade_after: int = 3,
                 stem_path: str = "auto",
                 stem_impl: str | None = None, **core):
        """``evict`` defaults to drop-newest: an admitted stream is a
        promise held for its whole lifetime (unlike single frames, where
        freshness beats fairness and the vision engine drops oldest).
        ``degrade_after``: launch-fault count after which the stem falls
        back to the patches reference conv; ``core`` forwards the
        scheduler's fault-tolerance knobs (DESIGN.md §10).

        ``stem_path``: ``"gated"`` fuses the delta-gate select into the
        stem kernel (one launch, skipped slots pay no stem FLOPs —
        DESIGN.md §3.6, single-device only); ``"where"`` is the
        compute-all reference select; ``"auto"`` picks gated on a TPU
        single-device engine, where otherwise.  ``stem_impl`` forces the
        where-path conv impl (tests pass ``"pallas"`` so the reference
        is the same kernel family the gated path fuses)."""
        if cfg.variant != "p2m":
            raise ValueError("StreamEngine requires the p2m variant: stem "
                             "caching and readout accounting are defined by "
                             "the in-pixel layer")
        super().__init__(max_streams, max_queue=max_queue, evict=evict,
                         **core)
        self.cfg = cfg
        self.degrade_after = degrade_after
        self._kernel_faults = 0
        self._gate_faults = 0
        self.det_cfg = det_cfg
        self.gate_cfg = gate
        self.mesh = mesh
        self._params = params
        self._bn = bn_state
        self._det = det_params
        dep = deploy_params(params["stem"], bn_state["stem"], cfg.p2m)
        if deploy_quant_bits is not None:
            dep = quantize_deploy(
                dep, QuantSpec(deploy_quant_bits, deploy_quant_bits))
        self._deploy = dep
        self.geom = FirstLayerGeom(
            image_size=cfg.image_size, kernel=cfg.p2m.kernel, padding=0,
            stride=cfg.p2m.stride, out_channels=cfg.p2m.out_channels,
            out_bits=cfg.p2m.n_bits)
        self._iou_thresh = iou_thresh

        if stem_path == "auto":
            stem_path = ("gated" if mesh is None
                         and jax.default_backend() == "tpu" else "where")
        if stem_path not in ("gated", "where"):
            raise ValueError(f"unknown stem_path {stem_path!r}")
        self.stem_path = stem_path
        self._stem_impl = stem_impl
        # in-kernel skip accounting over *active* slots (gated path only:
        # the where path computes every slot regardless)
        self._stem_total = 0
        self._stem_skipped = 0

        ho = cfg.p2m.out_spatial(cfg.image_size)
        co = cfg.p2m.out_channels
        # device-resident across ticks: _launch feeds the previous tick's
        # stem output straight back in (no host round-trip; under a mesh
        # it stays sharded — see _stream_forward_for's out_shardings)
        self._cached_stem = jnp.zeros((self.n_slots, ho, ho, co),
                                      jnp.float32)
        self._gates: list[DeltaGate | None] = [None] * self.n_slots
        self._trackers: list[Tracker | None] = [None] * self.n_slots
        self._fwd = _stream_forward_for(cfg, det_cfg, mesh, self.n_slots,
                                        stem_impl, stem_path)
        # stream-specific registry views alongside the core's
        # latency/health (DESIGN.md §13.2): the aggregate stream summary
        # and the per-slot delta-gate ledgers
        self.registry.register_view(self.metrics_scope, "stream",
                                    self.stream_summary)
        self.registry.register_view(self.metrics_scope, "gates",
                                    self._gate_ledgers)

    # ------------------------------------------------- adapter hooks

    def submit(self, req: StreamRequest) -> str:
        """Reject degenerate streams at the door: an empty stream would
        otherwise occupy a slot whose launch has no frame to read."""
        if req.n_frames == 0:
            raise ValueError(f"stream {req.uid} has no frames")
        return super().submit(req)

    def _on_admit(self, i: int, req: StreamRequest) -> None:
        """Recycle slot ``i`` for a new stream: fresh gate (no reference
        frame), fresh tracker (ids restart at 0), zeroed stem cache —
        nothing of the previous occupant may leak.  The request reads
        its bandwidth numbers through the gate's ledger."""
        self._gates[i] = DeltaGate(self.gate_cfg, self.geom)
        self._trackers[i] = Tracker(iou_thresh=self._iou_thresh)
        self._cached_stem = self._cached_stem.at[i].set(0.0)
        req.ledger = self._gates[i].ledger

    def _on_launch_fault(self, exc: Exception) -> None:
        """Degradation ladder, rung 1 (DESIGN.md §10): repeated kernel
        faults swap the fused stem conv for the patches reference path —
        the stream keeps serving on the slow-but-solid conv."""
        self._kernel_faults += 1
        if self.degraded is None and self._kernel_faults >= self.degrade_after:
            self.degraded = "patches"
            # the ladder lands on the compute-all where-select: a faulting
            # fused/gated kernel is exactly what it must route around
            self.stem_path = "where"
            self._fwd = _stream_forward_for(self.cfg, self.det_cfg,
                                            self.mesh, self.n_slots,
                                            "patches", "where")

    def _launch(self, active):
        h = w = self.cfg.image_size
        images = np.zeros((self.n_slots, h, w, 3), np.float32)
        rerun = np.zeros((self.n_slots,), bool)
        frames: dict[int, np.ndarray] = {}
        for i, req in active:
            frame = req.frames[req.frames_done]
            frames[i] = frame
            images[i] = frame
            gate = self._gates[i]
            was_disabled = gate.disabled
            rerun[i] = gate.should_rerun(frame)
            if gate.disabled and not was_disabled:
                self._gate_faults += 1  # reference failed validation
        stem, boxes, scores, rerun_eff = self._fwd(
            self._params, self._bn, self._deploy, self._det,
            jnp.asarray(images), self._cached_stem, jnp.asarray(rerun))
        jax.block_until_ready((stem, boxes, scores))
        self._cached_stem = stem  # stays on device (sharded under a mesh)
        rerun_eff = np.asarray(rerun_eff)
        if self.stem_path == "gated":
            # every active slot whose effective mask is False had its stem
            # tile short-circuited in-kernel — zero MXU work, by design
            self._stem_total += len(active)
            self._stem_skipped += sum(
                1 for i, _ in active if not rerun_eff[i])
        for i, req in active:  # the per-stream ledger meters the tick
            if rerun_eff[i] and not rerun[i]:
                # the on-device check caught a corrupted stem cache:
                # degradation ladder rung 2 — this stream's gate drops to
                # dense (every remaining frame re-runs; the ledger stays
                # honest because it meters the *effective* mask)
                self._gates[i].disable()
                self._gate_faults += 1
            self._gates[i].observe(frames[i], bool(rerun_eff[i]))
        return np.asarray(boxes), np.asarray(scores)

    def _absorb(self, i: int, req: StreamRequest, result) -> bool:
        boxes, scores = result
        req.frame_outputs.append((boxes[i].copy(), scores[i].copy()))
        keep = scores[i] >= self.det_cfg.score_thresh
        live = self._trackers[i].update(boxes[i][keep], scores[i][keep])
        req.tracks.append([(t.tid, t.box.copy(), t.score) for t in live])
        req.frames_done += 1
        return req.frames_done >= req.n_frames

    # ------------------------------------------------------ reporting

    def _gate_ledgers(self) -> list:
        """Per-slot delta-gate ledger summaries (None = free slot) — the
        registry view that puts the readout-bandwidth accounting on the
        same snapshot surface as the latency ledgers."""
        return [None if g is None else g.ledger.summary()
                for g in self._gates]

    def health(self) -> dict:
        """Core health report plus the stream-specific degradation
        counters: gates dropped to dense (corrupted cache or reference)
        and kernel faults absorbed by the conv fallback."""
        h = super().health()
        h["gate_faults"] = self._gate_faults
        h["kernel_faults"] = self._kernel_faults
        return h

    def stream_summary(self) -> dict:
        """Aggregate stream metrics over completed requests: mean stem
        skip rate, measured bits/frame vs dense, and the measured
        bandwidth reduction on the served traffic (summed over the
        per-stream ledgers)."""
        done: list[StreamRequest] = self.completed
        frames = sum(r.frames_done for r in done)
        skips = sum(r.skip_count for r in done)
        bits = sum(r.bits for r in done)
        dense = frame_output_bits(self.geom)
        bpf = bits / frames if frames else 0.0
        return {
            "streams": len(done),
            "frames": frames,
            "stem_skip_rate": skips / frames if frames else 0.0,
            "bits_per_frame": bpf,
            "dense_bits_per_frame": dense,
            "measured_reduction_vs_dense": dense / bpf if bpf else 0.0,
            "stem_path": self.stem_path,
            # gated path only: fraction of active-slot stem computations
            # the fused kernel short-circuited (0.0 on the where path,
            # which computes every slot)
            "stem_flops_skipped_ratio": (
                self._stem_skipped / self._stem_total
                if self._stem_total else 0.0),
        }
