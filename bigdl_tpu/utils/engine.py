"""Runtime bootstrap — the TPU-native equivalent of BigDL's ``Engine``.

Reference: scala/dllib/.../utils/Engine.scala — detects node/core counts from
the Spark conf, selects an engine type (MklBlas | MklDnn) and owns thread
pools. Here the "cluster" is a JAX device mesh: ``Engine.init`` initialises
jax.distributed (multi-host, when applicable), discovers local/global devices,
and builds the default :class:`jax.sharding.Mesh` that the rest of the
framework (DistriOptimizer, Keras fit, Orca Estimator) trains over.

Engine types:
- ``"tpu"``  — compile to the TPU backend (the whole point).
- ``"cpu"``  — host CPU backend; with ``XLA_FLAGS=--xla_force_host_platform_
  device_count=N`` this gives an N-device virtual mesh, the moral equivalent
  of the reference's ``local[N]`` Spark mode used by its distributed tests
  (SURVEY.md §4).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import threading
from typing import Optional, Sequence

import numpy as np

logger = logging.getLogger("bigdl_tpu")


@dataclasses.dataclass
class EngineConfig:
    engine_type: str = "tpu"          # "tpu" | "cpu" | "gpu"
    node_number: int = 1              # number of host processes
    core_number: int = 1              # devices per host (was: cores per executor)
    mesh_axes: tuple = ("data",)      # default mesh axis names
    mesh_shape: Optional[tuple] = None
    coordinator_address: Optional[str] = None
    process_id: int = 0


class Engine:
    """Global runtime singleton (ref: Engine.scala object Engine)."""

    _lock = threading.RLock()
    _initialized = False
    _config: EngineConfig = EngineConfig()
    _mesh = None

    # Axis-name conventions used across the framework. BigDL only has data
    # parallelism (SURVEY.md §2.5); tensor/sequence/expert/pipeline axes are
    # the idiomatic TPU extensions used by bigdl_tpu.llm / parallel.
    DATA_AXIS = "data"
    MODEL_AXIS = "model"
    SEQ_AXIS = "seq"
    EXPERT_AXIS = "expert"
    PIPELINE_AXIS = "pipe"

    @classmethod
    def init(
        cls,
        engine_type: Optional[str] = None,
        mesh_shape: Optional[Sequence[int]] = None,
        mesh_axes: Optional[Sequence[str]] = None,
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
    ):
        """Initialise the runtime and build the default device mesh.

        Multi-host: pass ``coordinator_address``/``num_processes``/
        ``process_id`` (or set JAX_COORDINATOR_ADDRESS etc.) and every host
        calls ``Engine.init`` — the analog of each Spark executor joining the
        BlockManager cluster in the reference's ``Engine.init``.
        """
        import jax

        from bigdl_tpu.utils.conf import conf

        with cls._lock:
            # layered config (ref: Engine.createSparkConf property
            # injection): call-site kwargs > conf.set > env > conf file
            # > defaults — see bigdl_tpu.utils.conf
            coordinator_address = (coordinator_address
                                   or conf.get("bigdl.coordinator.address")
                                   or None)
            num_processes = (num_processes
                             or conf.get_int("bigdl.num.processes"))
            if process_id is None:
                process_id = conf.get_int("bigdl.process.id")
            if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
                # explicit configuration (kwarg / conf.set / BIGDL env)
                # must fail LOUDLY: a multi-host job whose distributed
                # init silently fell back to single-process would train
                # on 1/N of the data and report success (ISSUE 10
                # satellite — this was a logger.debug). The
                # JAX_COORDINATOR_ADDRESS leg stays best-effort by
                # design: that env var is commonly injected by cluster
                # runtimes onto EVERY process of mixed jobs, where
                # running standalone is a legitimate outcome — but the
                # failure is still warned and counted
                # (bigdl_engine_init_failures_total), never silent.
                explicit = bool(coordinator_address)
                try:
                    jax.distributed.initialize(
                        coordinator_address=coordinator_address,
                        num_processes=num_processes,
                        process_id=process_id,
                    )
                except Exception as e:  # noqa: BLE001 — triaged below
                    if isinstance(e, RuntimeError) and \
                            "already" in str(e).lower():
                        # idempotent re-init: not a failure
                        logger.debug(
                            "jax.distributed.initialize skipped: %s", e)
                    else:
                        cls._count_init_failure()
                        if explicit:
                            raise RuntimeError(
                                "jax.distributed.initialize failed for "
                                "the explicitly configured coordinator "
                                f"{coordinator_address!r} (num_processes="
                                f"{num_processes}, process_id="
                                f"{process_id}): {e}") from e
                        logger.warning(
                            "best-effort jax.distributed init from env "
                            "autodetect failed; continuing single-"
                            "process: %s", e)

            requested = (engine_type or conf.get("bigdl.engine.type")
                         or os.environ.get("BIGDL_ENGINE_TYPE"))
            backend = requested or jax.default_backend()
            if requested == "tpu" and jax.default_backend() != "tpu":
                # the mesh below is built from jax.devices() whatever
                # the label says: an explicit "tpu" on a host that lost
                # (or never had) the chip must not train on a CPU mesh
                # called "tpu"
                raise RuntimeError(
                    "engine type 'tpu' was requested but JAX's default "
                    f"backend is {jax.default_backend()!r} "
                    f"({jax.devices()[0].device_kind}); is the chip "
                    "held by another process?")
            devices = jax.devices()
            local = jax.local_devices()
            if mesh_axes:
                axes = tuple(mesh_axes)
            else:
                axes = tuple(conf.get_list("bigdl.mesh.axes", ["data"]))
            if mesh_shape:
                shape = tuple(mesh_shape)
            else:
                cs = conf.get_list("bigdl.mesh.shape")
                shape = tuple(int(v) for v in cs) if cs else None
            if shape is None:
                shape = cls._default_shape(len(devices), axes)
            if math.prod(shape) != len(devices):
                raise ValueError(
                    f"mesh_shape {shape} does not cover {len(devices)} devices"
                )

            from jax.sharding import Mesh

            dev_array = np.asarray(devices).reshape(shape)
            cls._mesh = Mesh(dev_array, axes)
            cls._config = EngineConfig(
                engine_type=backend,
                node_number=jax.process_count(),
                core_number=len(local),
                mesh_axes=axes,
                mesh_shape=shape,
                coordinator_address=coordinator_address,
                process_id=jax.process_index(),
            )
            cls._initialized = True
            logger.info(
                "Engine initialized: backend=%s devices=%d hosts=%d mesh=%s%s",
                backend, len(devices), cls._config.node_number, axes, shape,
            )
            return cls._mesh

    @staticmethod
    def _count_init_failure():
        from bigdl_tpu import observability as obs
        if obs.enabled():
            obs.counter(
                "bigdl_engine_init_failures_total",
                "jax.distributed.initialize failures during "
                "Engine.init").inc()

    @classmethod
    def reinit_distributed(
            cls,
            coordinator_address: str,
            num_processes: Optional[int] = None,
            process_id: Optional[int] = None,
            **kwargs,
    ):
        """Rejoin a NEW distributed world (ISSUE 10): tear down the
        live jax.distributed client — the old coordinator died with
        the failed worker set — and run a fresh :meth:`init` against
        the next generation's coordinator. Shutdown is best-effort (a
        client wedged on a dead peer may refuse to close cleanly);
        the re-init itself follows the loud-failure contract above,
        so a rejoin that cannot reach the new coordinator raises
        instead of limping on solo."""
        import jax

        with cls._lock:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001 — wedged client
                logger.warning(
                    "jax.distributed.shutdown during rejoin failed "
                    "(continuing to re-init): %s", e)
            cls._initialized = False
            cls._mesh = None
        return cls.init(coordinator_address=coordinator_address,
                        num_processes=num_processes,
                        process_id=process_id, **kwargs)

    @staticmethod
    def _default_shape(n_devices: int, axes: Sequence[str]) -> tuple:
        if len(axes) == 1:
            return (n_devices,)
        # put everything on the first axis by default
        return (n_devices,) + (1,) * (len(axes) - 1)

    @classmethod
    def mesh(cls):
        if not cls._initialized:
            cls.init()
        return cls._mesh

    @classmethod
    def config(cls) -> EngineConfig:
        return cls._config

    @classmethod
    def node_number(cls) -> int:
        return cls._config.node_number

    @classmethod
    def core_number(cls) -> int:
        return cls._config.core_number

    @classmethod
    def is_initialized(cls) -> bool:
        return cls._initialized

    @classmethod
    def reset(cls):
        with cls._lock:
            cls._initialized = False
            cls._mesh = None
            cls._config = EngineConfig()


def init_engine(**kwargs):
    """Python-API parity shim (ref: python dllib utils/engine.py init_engine)."""
    return Engine.init(**kwargs)


def get_mesh():
    return Engine.mesh()


def train_rng_key(seed: int = 0):
    """RNG key for training loops (dropout masks etc.).

    On TPU this returns a key for the hardware RBG generator: threefry
    dropout masks cost ~40% of a BERT-base fine-tune step on v5e
    (measured: batch 64, dropout 0.1 — threefry 992 samples/s / MFU
    0.36, RBG 1517 / MFU 0.52, dropout-off ceiling 1746 / MFU 0.60).
    Elsewhere it stays threefry for bit-exact test determinism. RBG is
    counter-based and splittable; it is not a cryptographic stream, which
    dropout does not need.
    """
    import jax

    if jax.default_backend() == "tpu":
        return jax.random.key(seed, impl="rbg")
    return jax.random.PRNGKey(seed)
