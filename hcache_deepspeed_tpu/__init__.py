"""hcache_deepspeed_tpu: a TPU-native training & inference framework with the
capabilities of DeepSpeed v0.16.8 + the HCache KV-restoration fork.

Reference analog of this module: ``deepspeed/__init__.py`` —
``initialize`` (:69), ``init_inference`` (:291), ``add_config_arguments``
(:268). See SURVEY.md for the full component mapping.
"""

from .version import __version__

from . import comm  # noqa: F401
from .platform import get_platform  # noqa: F401
from .runtime.config import HDSConfig, load_config  # noqa: F401
from .runtime.engine import HDSEngine
from .runtime.hybrid_engine import HybridEngine  # noqa: F401
from .utils.compile_cache import ensure_compile_cache
from .utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               config=None,
               config_params=None,
               mesh_param=None,
               *,
               init_params=None,
               example_batch=None,
               loss_fn=None,
               topology=None,
               tp_spec_fn=None,
               batch_spec_fn=None):
    """Initialize the engine. Reference: ``deepspeed.initialize``
    (``deepspeed/__init__.py:69``) — returns the same 4-tuple
    ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    TPU-specific arguments:
      init_params     pre-built parameter pytree (else the flax model is
                      initialised sharded from ``example_batch``)
      example_batch   a host pytree with the micro-batch shapes
      loss_fn         optional ``loss_fn(model_outputs, batch) -> scalar``
      topology        an existing MeshTopology (else built from config.mesh)
      tp_spec_fn      ``(path, leaf) -> PartitionSpec`` tensor-parallel rules
      batch_spec_fn   ``(leaf) -> PartitionSpec`` override for batch sharding
    """
    assert model is not None, "deepspeed.initialize requires a model"
    cfg = load_config(config if config is not None else config_params)
    ensure_compile_cache()
    comm.init_distributed()
    # apply an EXPLICIT comms_logger config block to the global logger
    # (reference: comms_config.py wired through deepspeed.initialize);
    # a config without the block must not clobber programmatic
    # comm.configure() state with defaults
    if "comms_logger" in cfg.model_fields_set:
        cl = cfg.comms_logger
        comm.configure(enabled=cl.enabled, verbose=cl.verbose,
                       prof_all=cl.prof_all, prof_ops=list(cl.prof_ops),
                       debug=cl.debug)

    from .runtime.pipe.module import PipelineModule
    engine_cls = HDSEngine
    if isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine
        engine_cls = PipelineEngine
    engine = engine_cls(model,
                        cfg,
                        init_params=init_params,
                        example_batch=example_batch,
                        loss_fn=loss_fn,
                        optimizer=optimizer,
                        lr_scheduler=lr_scheduler,
                        topology=topology,
                        tp_spec_fn=tp_spec_fn,
                        batch_spec_fn=batch_spec_fn,
                        training_data=training_data)
    return engine, engine.optimizer_def, engine.training_dataloader, \
        engine.lr_scheduler


def add_config_arguments(parser):
    """Reference: deepspeed/__init__.py:233 — argparse plumbing."""
    group = parser.add_argument_group("HDS-TPU",
                                      "HDS-TPU configuration arguments")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable the engine (parity flag).")
    group.add_argument("--deepspeed_config", "--hds_config", default=None,
                       type=str, help="Path to the JSON config.")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS


def tp_model_init(model, tp_size: int = 1, dtype=None, config=None,
                  **kwargs):
    """Prepare a model for tensor-parallel training/inference.

    Reference: ``deepspeed.tp_model_init`` (``deepspeed/__init__.py:369``)
    — there it rewrites nn.Modules into ``LinearLayer``/
    ``LinearAllreduce``; here sharding is declarative, so this ensures a
    topology with a ``tensor`` axis of ``tp_size`` exists and returns the
    model unchanged — ``initialize``'s AutoTP derives the PartitionSpecs
    from the parameter tree (``parallel/auto_tp.py``).
    """
    from .parallel import topology as topo_mod
    topo = topo_mod._topology   # None unless explicitly initialized
    if topo is None:
        topo_mod.initialize_topology(topo_mod.TopologySpec(tensor=tp_size))
    elif topo.tensor_size != tp_size:
        raise ValueError(
            f"active topology has tensor={topo.tensor_size}, requested "
            f"tp_size={tp_size}; reset the topology first")
    return model


def init_inference(model=None, config=None, **kwargs):
    """Reference: deepspeed/__init__.py:291. Implemented by the inference
    package (ragged batching engine v2 + HCache restore)."""
    try:
        from .inference import build_engine
    except ImportError as e:
        raise NotImplementedError(
            "the inference engine is not available in this build: "
            f"{e}") from e
    return build_engine(model=model, config=config, **kwargs)
