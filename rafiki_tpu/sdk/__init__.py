"""Model SDK (L1): the contract user model templates implement, plus the
JAX/XLA training backend, knob types, dataset utilities, parameter
serialization, and structured in-model logging.

Reference analogue: rafiki/model/ (SURVEY.md §2.1)."""

from rafiki_tpu.sdk.dataset import dataset_utils  # noqa: F401
from rafiki_tpu.sdk.jax_backend import (  # noqa: F401
    DataParallelTrainer,
    cached_trainer,
    classification_accuracy,
    softmax_classifier_loss,
    trainer_ensemble_stack,
    tunable_optimizer,
)
from rafiki_tpu.sdk.knob import (  # noqa: F401
    BaseKnob,
    CategoricalKnob,
    FixedKnob,
    FloatKnob,
    IntegerKnob,
    deserialize_knob_config,
    serialize_knob_config,
)
from rafiki_tpu.sdk.log import (  # noqa: F401
    ModelLogger,
    StopTrialEarly,
    logger,
    parse_logs,
)
from rafiki_tpu.sdk.population import PopulationTrainer  # noqa: F401
from rafiki_tpu.sdk.model import (  # noqa: F401
    BaseModel,
    GenerationSpec,
    InvalidModelClassError,
    PopulationSpec,
    draft_capability,
    generation_capability,
    load_model_class,
    population_capability,
    sampling_capability,
    spec_verify_capability,
    test_model_class,
    validate_model_dependencies,
)
from rafiki_tpu.sdk.params import dump_params, load_params  # noqa: F401
