"""The system under test, as the benchmark builds it: its configuration
object from a configuration file, and weights made from the seed."""
from __future__ import annotations


def mnv2_config(cfg: dict):
    from repro.core.p2m_conv import P2MConvConfig
    from repro.models.mobilenetv2 import MNV2Config

    keys = ("variant", "image_size", "num_classes", "width", "head_channels",
            "last_block_div", "first_channels")
    extra = {"p2m": P2MConvConfig(**cfg["p2m"])} if "p2m" in cfg else {}
    return MNV2Config(**{k: cfg[k] for k in keys}, **extra)


def weights(run, ref):
    """(params, state) made on the device in one jitted call from the
    run's seed, by the configuration's reference initialiser."""
    import jax

    from bench import harness

    return jax.jit(lambda k: ref.init(k, run.cell.cfg))(
        harness.seed_key(run.seed))
