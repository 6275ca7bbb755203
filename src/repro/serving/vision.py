"""Batched vision serving: microbatched single-shot inference through
the deploy-folded P²M stem + MobileNetV2 backbone (DESIGN.md §7–§8).

``VisionEngine`` is a thin adapter over the shared scheduler core
(`serving/scheduler.py`): the LM engine keeps a request in its slot for
many decode ticks; the vision workload is single-shot, so a slot here is
a position in a fixed-shape microbatch that a request occupies for
exactly one tick — ``_absorb`` always reports "finished" and the core
recycles every slot every tick.  Free slots carry a zero image and their
logits are discarded, keeping the jitted computation shape-stable.

The forward is the *deployed* model: for the P²M variant the stem runs
with BN folded into the pixel weights and (optionally) PTQ-quantized —
i.e. what the manufactured sensor + SoC would execute, served through
the fused implicit-im2col conv path (`core.p2m_conv._resolve_impl`).

Scale-out (``mesh=``): pass a data mesh and the padded microbatch is
split across devices under the pure-DP vision plan (DESIGN.md §7.1 —
`vision_plan_for`; params/BN/deploy trees replicate, the image batch
dim shards, the probs come back replicated).  The adapter is otherwise
identical, so every queue/eviction/latency test holds sharded as-is.

The bounded queue evicts the *oldest* waiting request on overflow (the
always-on-sensor policy: stale frames are worthless; fresh ones are
not).  Per-request latency accounting comes from the core: ticks spent
queued, the serving tick, and the wall-clock of the launch that served
it — enough to read queueing delay and batch amortization separately.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.p2m_vww import (
    SERVE_MAX_BATCH,
    SERVE_MAX_QUEUE,
    SERVE_QUANT_BITS,
)
from repro.core.bn_fold import deploy_params
from repro.core.pixel_model import PixelModel
from repro.core.quant import QuantSpec, quantize_deploy
from repro.models.mobilenetv2 import MNV2Config, apply_mnv2
from repro.obs.metrics import counted_lru_cache
from repro.parallel import under_plan, vision_plan_for
from repro.parallel.sharding_utils import batch_shardings
from repro.serving.scheduler import ScheduledRequest, SlotEngine


@dataclasses.dataclass
class VisionRequest(ScheduledRequest):
    uid: int
    image: np.ndarray  # (H, W, 3) float32 in [0, 1]

    # Filled by the engine:
    label: int | None = None
    probs: np.ndarray | None = None

    @property
    def batch_wall_us(self) -> float:
        """Wall-clock of the (single) launch that served this request."""
        return self.launch_wall_us


def _make_forward(cfg: MNV2Config, pixel_model: PixelModel | None,
                  impl: str | None = None):
    def forward(params, bn, dep, images):
        logits, _ = apply_mnv2(params, bn, images, cfg, pixel_model,
                               train=False, p2m_deploy=dep, p2m_impl=impl)
        return jax.nn.softmax(logits, axis=-1)

    return forward


def _jit_forward(forward, cfg: MNV2Config, mesh: Mesh | None,
                 batch: int | None):
    """Jit the deploy forward, optionally under the data mesh: the
    microbatch is split over the data axes of the pure-DP vision plan
    (DESIGN.md §7.1) while the small param/BN/deploy trees replicate;
    probabilities return replicated so the host-side slot bookkeeping
    never changes."""
    if mesh is None:
        return jax.jit(forward)
    plan = vision_plan_for(mesh)
    h = w = cfg.image_size
    img = batch_shardings(
        jax.ShapeDtypeStruct((batch, h, w, 3), jnp.float32), plan)
    rep = NamedSharding(mesh, P())
    # the plan runs the stem kernel per batch shard
    return jax.jit(under_plan(forward, plan),
                   in_shardings=(rep, rep, rep, img), out_shardings=rep)


@counted_lru_cache("deploy_forward")
def _deploy_forward_for(cfg: MNV2Config, mesh: Mesh | None = None,
                        batch: int | None = None, impl: str | None = None):
    """Deploy-mode forward, jitted once per (config, mesh, conv impl) —
    params, BN state and the folded deploy tree ride as traced arguments
    so every engine on this config shares one compilation (metered:
    ``compile_cache.deploy_forward.*`` in the metrics registry).
    ``impl`` selects the stem conv path; the fault-degradation ladder
    requests ``"patches"`` (the reference conv) after repeated kernel
    faults."""
    return _jit_forward(_make_forward(cfg, None, impl), cfg, mesh, batch)


class VisionEngine(SlotEngine):
    request_type = VisionRequest

    def __init__(self, params, bn_state, cfg: MNV2Config, *,
                 pixel_model: PixelModel | None = None,
                 max_batch: int = SERVE_MAX_BATCH,
                 max_queue: int = SERVE_MAX_QUEUE,
                 deploy_quant_bits: int | None = SERVE_QUANT_BITS,
                 mesh: Mesh | None = None,
                 evict: str = "drop-oldest",
                 degrade_after: int = 3, **core):
        """``deploy_quant_bits``: PTQ bit-width for the folded P²M stem
        (None ⇒ fold only, no quantization; ignored for the baseline
        variant, which has no in-pixel layer to fold).  ``mesh``: shard
        the microbatch over the mesh's data axes (None ⇒ single device).
        ``degrade_after``: launch-fault count after which the engine
        falls back from the fused conv to the patches reference path
        (DESIGN.md §10); ``core`` forwards the scheduler's
        fault-tolerance knobs and the front door's ``tick_cost``
        cadence declaration (a one-tick microbatch is cheaper than an
        LM launch and dearer than a stream frame, DESIGN.md §11) to
        `SlotEngine`.  Pool several engines (one per submesh of
        `launch.mesh.make_submeshes`) behind a
        `serving.pool.ReplicaPool` for replica-parallel serving.
        """
        super().__init__(max_batch, max_queue=max_queue, evict=evict, **core)
        self.cfg = cfg
        self.mesh = mesh
        self.degrade_after = degrade_after
        self._kernel_faults = 0
        self._params = params
        self._bn = bn_state
        self._pixel_model = pixel_model

        dep = None
        if cfg.variant == "p2m":
            dep = deploy_params(params["stem"], bn_state["stem"], cfg.p2m)
            if deploy_quant_bits is not None:
                dep = quantize_deploy(
                    dep, QuantSpec(deploy_quant_bits, deploy_quant_bits))
        self._deploy = dep

        if pixel_model is None:
            self._fwd = _deploy_forward_for(cfg, mesh, max_batch)
        else:  # PixelModel trees aren't hashable — private compilation,
            # but the mesh (if any) still applies
            self._fwd = _jit_forward(_make_forward(cfg, pixel_model),
                                     cfg, mesh, max_batch)

    # ------------------------------------------------- adapter hooks

    def _on_launch_fault(self, exc: Exception) -> None:
        """Degradation ladder, rung 1 (DESIGN.md §10): after
        ``degrade_after`` launch faults, swap the fused-conv forward for
        the patches reference path — the kernel that keeps failing stops
        being on the serving path, and the engine keeps answering."""
        self._kernel_faults += 1
        if self.degraded is None and self._kernel_faults >= self.degrade_after:
            self._degrade_to_patches()

    def _degrade_to_patches(self) -> None:
        self.degraded = "patches"
        if self._pixel_model is None:
            self._fwd = _deploy_forward_for(self.cfg, self.mesh,
                                            self.n_slots, "patches")
        else:
            self._fwd = _jit_forward(
                _make_forward(self.cfg, self._pixel_model, "patches"),
                self.cfg, self.mesh, self.n_slots)

    def _launch(self, active):
        h = w = self.cfg.image_size
        images = np.zeros((self.n_slots, h, w, 3), np.float32)
        for i, req in active:
            images[i] = req.image
        probs = self._fwd(self._params, self._bn, self._deploy,
                          jnp.asarray(images))
        return np.asarray(jax.block_until_ready(probs))

    def _absorb(self, i, req: VisionRequest, probs) -> bool:
        req.probs = probs[i]
        req.label = int(probs[i].argmax())
        return True  # a vision slot lives exactly one tick
