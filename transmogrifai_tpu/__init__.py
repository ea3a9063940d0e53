"""transmogrifai_tpu — a TPU-native AutoML framework for structured data.

A ground-up JAX/XLA re-design with the capabilities of TransmogrifAI (Salesforce's
Spark-based AutoML library): typed features, a lazy stage DAG, automatic per-type feature
engineering (Transmogrifier), automatic feature validation (SanityChecker,
RawFeatureFilter), automatic model selection with cross-validation, evaluators, and model
explainability — executing on row-sharded device arrays under ``jit`` over a
``jax.sharding.Mesh`` instead of Spark executors.
"""

__version__ = "0.1.0"

import time as _time

#: the clock before the first and after the last import below:
#: ``perf.timers.package_import_seconds()`` is their difference
_IMPORT_START = _time.perf_counter()

from .types import *  # noqa: F401,F403 — feature type hierarchy
from .features.feature import Feature, FeatureHistory
from .features.builder import FeatureBuilder
from .data.dataset import Column, Dataset
from .workflow.workflow import Workflow, WorkflowModel
from .ops.transmogrifier import transmogrify
from .checkers.sanity import SanityChecker
from .checkers.diagnostics import (  # noqa: F401 — opcheck static validation
    DagCycleError, Diagnostic, DiagnosticReport, OpCheckError, Severity,
)
from .models.selector import (
    BinaryClassificationModelSelector,
    MultiClassificationModelSelector,
    RegressionModelSelector,
    ModelSelector,
)
from .evaluators.base import Evaluators
from .local import export_standalone, score_function  # noqa: F401
from .readers.files import DataReaders
from .readers.joined import (  # noqa: F401
    JoinedReader, JoinType, TimeColumn, TimeBasedFilter,
)
from .readers.streaming import (  # noqa: F401
    JsonlTailSource, MicroBatchStreamingReader, OffsetCheckpoint,
)
from . import perf  # noqa: F401 — compile probe + persistent compilation cache
from .ops import bucketizers  # noqa: F401 — registers decision-tree bucketizer stages
from .ops import misc  # noqa: F401 — registers misc value transformers + scalers
from .ops import embeddings as _embeddings  # noqa: F401 — registers Word2Vec/LDA
from .ops import ner as _ner  # noqa: F401 — registers NameEntityRecognizer
from .ops import collections_lift as _lift  # noqa: F401 — registers map/list plumbing
from .models import combiner as _combiner  # noqa: F401 — registers SelectedModelCombiner
from . import dsl  # noqa: F401 — attaches the rich-feature DSL methods

_IMPORT_END = _time.perf_counter()

__all__ = [
    "Feature", "FeatureHistory", "FeatureBuilder", "Column", "Dataset",
    "Workflow", "WorkflowModel", "transmogrify", "SanityChecker",
    "BinaryClassificationModelSelector", "MultiClassificationModelSelector",
    "RegressionModelSelector", "ModelSelector", "Evaluators", "DataReaders",
    "score_function", "export_standalone", "MicroBatchStreamingReader",
    "OffsetCheckpoint", "JsonlTailSource",
    "Diagnostic", "DiagnosticReport", "Severity", "OpCheckError",
    "DagCycleError",
]
