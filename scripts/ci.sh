#!/usr/bin/env bash
# Tier-1 verification: the full test suite, a multi-device lane, and a
# short benchmark smoke of the P²M kernel stack with a regression gate —
# so kernel and scaling regressions are caught without a TPU.
# Usage: scripts/ci.sh  (or `make verify`)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
# (includes the video-subsystem tests — test_video_detect_track.py and
# test_video_stream.py — the fault-injection / deadline / containment
# tests in test_faults.py, and the observability tests in test_obs.py —
# tracer determinism / disabled-tracer freedom / registry-vs-legacy
# parity / compile-cache counters, DESIGN.md §13 — all in the default
# lane)
python -m pytest -x -q

echo "== multi-device lane (8 virtual CPU devices, in-process) =="
# The sharding-machinery tests marked needs8 only run here — including
# the sharded-VisionEngine parity tests in test_vision_serving.py (one
# engine tick, sharded microbatch == single device; DESIGN.md §8), the
# sharded-StreamEngine multi-tick parity tests in test_video_stream.py
# (DESIGN.md §9), the sharded fault-containment test in test_faults.py
# (launch quarantine under a data mesh, DESIGN.md §10), and the
# replica-pool-over-submeshes parity test in test_serve_pool.py (a
# 2-replica pool of mesh-sharded vision engines, DESIGN.md §11), and the
# sharded stateful-LM-session tests in test_sessions.py (slot-resident
# WKV state over a data mesh, bitwise vs single device; DESIGN.md
# §12.4); the rest of each file re-runs under the virtual-device
# topology as a bonus.
# (test_distributed.py spawns its own 8-device subprocesses from tier-1.)
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
  python -m pytest -x -q tests/test_sharding.py tests/test_vision_serving.py \
    tests/test_video_stream.py tests/test_faults.py tests/test_serve_pool.py \
    tests/test_sessions.py

echo "== benchmark smoke (p2m kernels + serving + video + chaos + saturation + wkv + sessions, reduced shapes) =="
# emits the p2m_video_stream_* rows the gate's skip-rate and
# measured-bandwidth floors read, the p2m_serve_chaos_* rows its
# completion-rate floors read (DESIGN.md §10), the
# p2m_serve_saturation_* rows its pool-scaling and lockstep-equivalence
# floors read (DESIGN.md §11), and the p2m_rwkv_wkv_* / p2m_lm_session_*
# rows its WKV-parity and session-determinism floors read (DESIGN.md
# §12).  The chaos bench also writes the gated Perfetto trace artifact
# benchmarks/results/trace_smoke.json and stamps the smoke row with the
# trace_deterministic / trace_valid bits the gate holds at 1.0
# (DESIGN.md §13).
python benchmarks/run.py --smoke

echo "== bench regression gate (vs BENCH_p2m_conv.json baseline) =="
# also re-validates the trace artifact's span schema (well-formed
# events, no orphaned request tracks, monotone tick stamps)
python scripts/bench_gate.py

echo "== chip lane (active when jax reports a tpu) =="
# On a TPU host the kernel tests re-run with their kernels compiled (off
# the TPU they run interpreted; compiled, a geometry Mosaic cannot lower
# must raise ValueError instead).  Their XLA references are fp32 tolerance
# checks, so they run at highest matmul precision, as on the CPU; the
# TPU default is one bf16 pass.  Then the chip smoke drives the paper's
# configuration through serving, streaming and training on compiled
# kernels (chip_smoke.py).  Each step is one process that exits before
# the next starts: one process per chip.
BACKEND="$(python -c 'import jax; print(jax.default_backend())')"
if [ "$BACKEND" = "tpu" ]; then
  JAX_DEFAULT_MATMUL_PRECISION=highest python -m pytest -x -q \
    tests/test_p2m_kernel.py tests/test_p2m_conv_fused.py \
    tests/test_p2m_conv_pipelined.py
  python chip_smoke.py
else
  echo "chip lane: skipped (backend=$BACKEND)"
fi

echo "verify: OK"
