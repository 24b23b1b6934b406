"""synapseml_tpu — a TPU-native distributed ML pipeline framework.

A from-scratch rebuild of the capabilities of SynapseML (MMLSpark) designed for TPU
hardware: composable Estimator/Transformer pipelines over partitioned columnar tables,
a histogram-GBDT trainer whose feature-histogram allreduce runs as XLA collectives over
the ICI mesh, an online linear / contextual-bandit learner with collective weight
averaging, an ONNX importer executing via jit/pjit, image featurization, HTTP service
transformers, low-latency serving, and a library of distributed ML tools (explainers,
tuning, recommenders, KNN, data balance). See SURVEY.md at the repo root for the
structural analysis of the reference this rebuild targets.
"""

__version__ = "0.1.0"

from .core import (  # noqa: F401
    ComplexParam,
    Estimator,
    Model,
    Param,
    ParamValidators,
    Params,
    Pipeline,
    PipelineModel,
    PipelineStage,
    Table,
    Transformer,
    UnaryTransformer,
    concat_tables,
    load_stage,
    save_stage,
)
from .runtime.compile_cache import place_compile_cache

# before any compile, in every process that imports the package; worker
# processes inherit the choice through the environment
place_compile_cache()
