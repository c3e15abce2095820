"""bigdl_tpu — a TPU-native re-architecture of BigDL (yctai/BigDL).

A from-scratch framework on jax/XLA/pjit/Pallas providing the reference's
capabilities (see SURVEY.md):

- ``bigdl_tpu.tensor``  — Tensor facade over ``jax.Array``
  (ref: scala/dllib .../tensor/DenseTensor.scala).
- ``bigdl_tpu.nn``      — module contract + layer zoo + criterions
  (ref: scala/dllib .../nn/; hand-written backwards replaced by jax autodiff).
- ``bigdl_tpu.optim``   — Local/Distri optimizers, OptimMethods, Triggers,
  ValidationMethods (ref: .../optim/DistriOptimizer.scala, AllReduceParameter
  replaced by XLA collectives compiled into the SPMD step).
- ``bigdl_tpu.feature`` — DataSet/Sample/MiniBatch/transformers
  (ref: .../feature/dataset/).
- ``bigdl_tpu.keras``   — Keras-style API (ref: .../dllib/keras/).
- ``bigdl_tpu.models``  — model zoo (ref: .../dllib/models/).
- ``bigdl_tpu.orca``    — scale-out Estimator runtime (ref: python/orca).
- ``bigdl_tpu.chronos`` — time-series toolkit (ref: python/chronos).
- ``bigdl_tpu.llm``     — low-bit LLM inference (ref: python/llm, ggml kernels
  replaced by Pallas INT4/INT8 kernels).
- ``bigdl_tpu.parallel``— mesh / collectives / ring-attention building blocks
  (no reference equivalent: BigDL is DP-only; see SURVEY.md §2.5).
- ``bigdl_tpu.observability`` — metric registry (Prometheus exposition)
  + trace spans (Chrome-trace export); see docs/OBSERVABILITY.md.
- ``bigdl_tpu.reliability`` — fault-injection sites + retry/deadline/
  breaker/health policies behind the SoCC'19 survive-failures claim;
  see docs/RELIABILITY.md.
"""

import os as _os


def _place_compile_cache() -> None:
    """Persistent XLA compile cache, placed before anything compiles.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it
    and nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache`` — a path fixed by where the package sits,
    so every process started from one checkout finds what another one
    compiled."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    package = _os.path.dirname(_os.path.abspath(__file__))
    jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(package), ".jax_cache"))


_place_compile_cache()

from bigdl_tpu.version import __version__  # noqa: E402
from bigdl_tpu.utils.engine import Engine, init_engine, get_mesh  # noqa: E402
from bigdl_tpu.utils.table import Table, T  # noqa: E402

__all__ = [
    "__version__",
    "Engine",
    "init_engine",
    "get_mesh",
    "Table",
    "T",
]
