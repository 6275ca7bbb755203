"""Compile the benchmark's training step ahead of time for one described
TPU v5e chip, and print its optimized HLO without metadata.

    JAX_PLATFORMS=cpu python scripts/step_hlo.py [--tree DIR] > step.txt

Nothing runs: the step of `bench/runners/train.py` is built from
``DIR``'s sources and `bench/configs/p2m_vww.json` (paper geometry,
batch 32), lowered with shapes only, and compiled by the TPU compiler
installed with JAX.  The printed text drops every ``metadata={...}``
and the module's source tables, and is compiled without traceback
locations, which Pallas kernels otherwise embed in their bodies.  Two
trees whose steps differ only in names and scopes then print the same
text, up to the names of instructions XLA derives from them.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path


def strip_metadata(text: str) -> str:
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    return re.sub(r"\nFileNames\n.*?(?=\n%|\nENTRY)", "\n", text, flags=re.S)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", type=Path,
                    default=Path(__file__).resolve().parents[1])
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args(argv)
    tree = args.tree.resolve()
    sys.path[:0] = [str(tree), str(tree / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    # The program picks its TPU kernels by the default backend, which
    # is the CPU here.
    jax.default_backend = lambda: "tpu"

    from bench import harness, program
    from repro.optim import constant, sgd
    from repro.train.vision import make_vww_train_step, vww_train_state

    cfg = json.loads((tree / "bench/configs/p2m_vww.json").read_text())
    ref = harness.load_module(tree / "bench/references/mnv2.py", "ref")
    opt = sgd(constant(cfg["train"]["lr"]), momentum=cfg["train"]["momentum"])

    def init(key):
        params, bn = ref.init(key, cfg)
        return vww_train_state(params, bn, opt.init(params))

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one,
                                       weak_type=a.weak_type),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    size = cfg["image_size"]
    batch = {"images": jax.ShapeDtypeStruct((args.batch, size, size, 3),
                                            jnp.float32, sharding=one),
             "labels": jax.ShapeDtypeStruct((args.batch,), jnp.int32,
                                            sharding=one)}
    step = jax.jit(make_vww_train_step(program.mnv2_config(cfg), opt))
    sys.stdout.write(strip_metadata(step.lower(state, batch).compile()
                                    .as_text()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
