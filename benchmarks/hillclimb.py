"""§Perf hillclimb: hypothesis → plan change → re-lower → measure terms.

Three cells (picked from the baseline roofline table):
  * qwen3-moe-30b-a3b × train_4k — worst roofline fraction (0.028) and most
    collective-bound (collective/compute ≈ 18×),
  * llama3.2-1b × train_4k — the over-sharded small-model case,
  * llama-3.2-vision-11b × train_4k — the arch that carries the paper's
    P²M frontend.

Each variant is a sharding-plan override (the model code is unchanged);
run_cell re-lowers + recompiles under tag "<cell>-<variant>" and the
resulting terms are compared against the cached baseline.  Hypotheses and
outcomes are logged to benchmarks/results/hillclimb.json and transcribed
into EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

NO_TP = {"heads": None, "kv_heads": None, "mlp": None, "vocab": None,
         "heads_act": None, "mlp_act": None, "vocab_act": None}

# round 1 results: benchmarks/results/hillclimb_round1.json
#   dp256 CONFIRMED (coll 1.27s -> 0.12s, frac 0.121 -> 0.775);
#   dp_fsdp REFUTED (unsharded vocab head psums f32 logits: coll 2.5s);
#   ep_data REFUTED (expert-data conflicts with batch-data: resharding);
#   no_fsdp NULL (identical terms: XLA had already hoisted the FSDP
#     gathers out of the layer loop — they were never the bottleneck);
#   fsdp_model_no_tp / tp_seq REFUTED (same vocab-psum trap + seq
#     resharding inflation).
# round 2 below incorporates the two lessons: (a) always keep the vocab
# head column-sharded, (b) attack the MoE dispatch volume by sharding
# *tokens* 256-way (seq over "model"), not by moving experts.
NO_ATTN_TP = {"heads": None, "kv_heads": None, "mlp": None,
              "heads_act": None, "mlp_act": None}

EXPERIMENTS = [
    ("llama3.2-1b", "train_4k", "dp256",
     "1.2B params fit replicated (2.5 GB bf16 + 10 GB fp32 opt); dropping "
     "TP removes per-layer activation psums (~50 GB/dev) leaving one grad "
     "all-reduce (~5 GB/dev f32) -> collective 1.27s -> ~0.1s, compute-bound",
     {"batch": ("data", "model"), "embed": None, "vocab": None,
      "vocab_act": None, **NO_ATTN_TP}),
    ("qwen3-moe-30b-a3b", "train_4k", "seq_model_ep_data",
     "dispatch a2a volume scales with tokens/device: sharding seq over "
     "'model' (tokens 256-way instead of 16-way) cuts it 16x (733 GB -> "
     "~60 GB/dev); experts move to 'data' (8/chip) with d_ff over 'model' "
     "so weights+opt stay 256-way sharded; GQA kv gathers for attention "
     "over sharded seq are small (kv_dim=512)",
     {"seq": "model", "expert": "data", "embed": None,
      "batch": ("pod", "data")}),
    ("qwen3-moe-30b-a3b", "train_4k", "attn_dp_cap1",
     "control for round-2: keep baseline EP, drop only attention TP "
     "(psums from attention are ~10% of the 733 GB) — expect a small win, "
     "bounding how much of the collective is attention vs dispatch",
     NO_ATTN_TP),
    ("llama-3.2-vision-11b", "train_4k", "fsdp_data_no_attn_tp",
     "round-1 failure isolated to the unsharded vocab head (33 GB f32 "
     "logit psums). Keep vocab column-sharded (no psum), drop only "
     "attention/MLP TP: per-layer activation psums (~290 GB/dev) vanish; "
     "FSDP-over-data weight gathers (~66 GB/dev incl remat) remain "
     "-> collective 6.0s -> ~1.5s, frac 0.21 -> ~0.45",
     {"batch": ("pod", "data"), "embed": "data", **NO_ATTN_TP}),
]

# round 2 results: seq_model_ep_data REFUTED (attention over model-sharded
#   seq forces replication/gathers: coll 54s); attn_dp_cap1 REFUTED
#   (removing TP idles the model axis: per-device FLOPs 8x); vision
#   fsdp_data_no_attn_tp: collective prediction CONFIRMED (6.0s -> 0.35s)
#   but same idle-axis compute blow-up (1.3s -> 15.1s). Lesson: every
#   mesh axis must carry either batch or model work.
# round 3: (a) MoE — keep the baseline compute layout but replace the
#   dispatch/combine with the shard_map local-combine path (one bf16
#   token-granular psum/layer instead of SPMD's fp32 slot-granular
#   all-reduce) + ZeRO-1 optimizer sharding so expert params need no
#   per-layer FSDP gathers; (b) vision — batch over BOTH axes (DP=256,
#   compute stays 256-way) with ZeRO-3-style weight sharding over "data".
ROUND3 = [
    ("qwen3-moe-30b-a3b", "train_4k", "shardmap_zero1",
     "SPMD places the MoE combine collective at slot granularity "
     "(fp32 (G,S*K,d) all-reduce = 733 GB/dev/step). shard_map combines "
     "locally per expert shard and psums ONCE per layer in bf16 at token "
     "granularity: k*2 = 16x less volume -> ~46 GB + attention psums; "
     "ZeRO-1 (opt over data) keeps memory at ~5 GB/dev without per-layer "
     "weight gathers",
     {"embed": None, "opt_embed": "data", "opt_mlp": "data"},
     {"moe_impl": "shard_map"}),
    ("llama-3.2-vision-11b", "train_4k", "dp256_zero3",
     "round-2 killed the psums but idled the model axis. Shard batch over "
     "BOTH axes (DP=256 -> compute back to baseline) and params over "
     "'data' (ZeRO-3, 1.4 GB/dev): collectives = hoisted weight gathers + "
     "one grad reduce-scatter; vocab head column-sharded via the weight "
     "(no logit psum)",
     {"batch": ("data", "model"), "embed": "data", **NO_ATTN_TP},
     None),
]


def p2m_block_hillclimb() -> None:
    """§Perf hillclimb for the P²M kernel block shapes (``--p2m-blocks``).

    Runs the `kernels.p2m_conv.tune` autotuner over the paper-geometry
    matmul and fused-conv signatures, then writes the per-candidate
    timings + winners to benchmarks/results/p2m_blocks.json.  On TPU this
    measures the real kernels; off-TPU it forces interpret mode on toy
    shapes — exercising the tuner machinery, not producing perf numbers
    (the JSON records which).
    """
    import jax

    from repro.core.pixel_model import default_pixel_model
    from repro.kernels.p2m_conv import tune
    from repro.kernels.p2m_conv.ops import _coeff_tuple

    coeffs = _coeff_tuple(default_pixel_model())
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        matmul_sigs = [(112 * 112, 75, 8), (8 * 112 * 112, 75, 8)]
        # paper geometry (560², k = s = 5); stride != kernel does not
        # compile for TPU (`conv.mosaic_conv_error`)
        conv_sigs = [(1, 560, 560, 3, 8, 5, 5), (8, 560, 560, 3, 8, 5, 5)]
    else:  # interpret mode: toy shapes, machinery-only
        matmul_sigs = [(256, 75, 8)]
        conv_sigs = [(1, 20, 20, 3, 8, 5, 5)]

    for m, k, n in matmul_sigs:
        best = tune.get_matmul_blocks(m, k, n, coeffs, "quant",
                                      enable=True, interpret=not on_tpu,
                                      iters=3 if on_tpu else 1)
        print(f"p2m_matmul M={m} K={k} N={n} -> blocks {best}")
    for b, h, w, c, n, kk, s in conv_sigs:
        bh, bn, depth = tune.get_conv_blocks(b, h, w, c, n, kk, s, coeffs,
                                             "quant", enable=True,
                                             interpret=not on_tpu,
                                             iters=3 if on_tpu else 1)
        print(f"p2m_conv B={b} {h}x{w}x{c} k={kk} s={s} -> "
              f"blocks (bh={bh}, bn={bn}, pipeline_depth={depth})")

    out = Path(__file__).resolve().parent / "results" / "p2m_blocks.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    tune.cache_dump(out)
    print(f"wrote {out}")


def term_summary(rec: dict) -> dict:
    from benchmarks.roofline import analyze_record

    a = analyze_record(rec)
    if a is None:
        return {"status": rec.get("error", "failed")[:200]}
    return {k: a[k] for k in ("compute_s", "memory_s", "collective_s",
                              "dominant", "roofline_fraction")}


def main() -> None:
    import sys as _sys

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--p2m-blocks" in _sys.argv:
        p2m_block_hillclimb()
        return

    from repro.launch.dryrun import run_cell

    exps = [e + (None,) for e in EXPERIMENTS]
    if "--round3" in _sys.argv:
        exps = list(ROUND3)
    results = []
    for arch, shape, variant, hypothesis, overrides, cfg_over in exps:
        base = run_cell(arch, shape, False)  # cached baseline
        rec = run_cell(arch, shape, False, force=True,
                       plan_overrides=overrides, tag=f"-{variant}",
                       cfg_overrides=cfg_over)
        entry = {
            "arch": arch, "shape": shape, "variant": variant,
            "hypothesis": hypothesis,
            "baseline": term_summary(base),
            "variant_terms": term_summary(rec),
        }
        results.append(entry)
        print(json.dumps(entry, indent=1, default=str))

    name = "hillclimb_round3.json" if "--round3" in _sys.argv else "hillclimb.json"
    out = Path(__file__).resolve().parent / "results" / name
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
