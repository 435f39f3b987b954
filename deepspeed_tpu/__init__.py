"""deepspeed_tpu — a TPU-native distributed training & inference framework
with the capabilities of DeepSpeed v0.7.1, re-designed for JAX/XLA/Pallas/pjit.

Public API mirrors the reference (`deepspeed/__init__.py:51/:225`):

    engine = deepspeed_tpu.initialize(model=model, config=cfg_dict_or_path)
    engine.train_batch(batch)          # fused compiled step
    engine.save_checkpoint(dir)

    infer = deepspeed_tpu.init_inference(model, config=...)
"""

__version__ = "0.1.0"
__git_branch__ = "main"

from .runtime.config import DeepSpeedConfig
from .runtime.engine import DeepSpeedEngine
from .runtime import activation_checkpointing as checkpointing  # noqa: F401
from .runtime import zero  # noqa: F401
from .telemetry import tracing
from .utils.logging import log_dist, logger
from . import comm

import sys as _sys

# reference spelling: ``import deepspeed.zero`` / ``from deepspeed.zero import Init``
_sys.modules[__name__ + ".zero"] = zero


def initialize(
    args=None,
    model=None,
    config=None,
    config_params=None,
    mesh=None,
    rng=None,
    model_parameters=None,
    optimizer=None,
    lr_scheduler=None,
    training_data=None,
    collate_fn=None,
    dist_init_required=None,
    **kwargs,
):
    """Build a training engine (reference: deepspeed/__init__.py:51).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` for signature
    parity; in the TPU-native design the optimizer and schedule are compiled
    into the engine's train step, so those slots return the engine's handles
    (optimizer=engine, lr_scheduler=engine.lr_schedule). When
    ``training_data`` is given, the third slot is a real DP-sharded
    ``DeepSpeedDataLoader`` over it (reference __init__.py:56 returns the
    engine's deepspeed_io loader the same way); otherwise it is None.
    """
    cfg = config if config is not None else config_params
    if cfg is None and args is not None:
        cfg = getattr(args, "deepspeed_config", None)
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    assert cfg is not None, "deepspeed_tpu.initialize: config is required"
    # the kept span ``startup/build`` (telemetry/tracing.py): the engine's
    # ``mesh`` and ``state`` phases, and every program traced, compiled or
    # loaded on the way, end under it
    with tracing.span(tracing.STARTUP, keep=True, role="train"):
        engine = DeepSpeedEngine(
            model=model, config=cfg, mesh=mesh, rng=rng, params=model_parameters, **kwargs
        )
    dataloader = None
    if training_data is not None:
        io_kw = {"collate_fn": collate_fn} if collate_fn is not None else {}
        dataloader = engine.deepspeed_io(training_data, **io_kw)
    return engine, engine, dataloader, engine.lr_schedule


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference: deepspeed/__init__.py:225)."""
    from .inference.engine import InferenceEngine

    return InferenceEngine(model=model, config=config or {}, **kwargs)


def init_distributed(dist_backend: str = "xla", **kwargs):
    comm.init_distributed(dist_backend=dist_backend, **kwargs)


def add_config_arguments(parser):
    """argparse plumbing (reference: deepspeed/__init__.py:209)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument(
        "--deepspeed", default=False, action="store_true", help="Enable DeepSpeed-TPU"
    )
    group.add_argument("--deepspeed_config", default=None, type=str, help="JSON config path")
    group.add_argument("--deepscale", default=False, action="store_true", help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
