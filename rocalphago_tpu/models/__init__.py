"""Neural networks (policy / value / rollout) + the JSON model-spec
registry. Parity: the reference's ``AlphaGo/models/`` (SURVEY.md §1 L3).
"""

from rocalphago_tpu.models.nn_util import (  # noqa: F401
    NEURALNETS,
    NeuralNetBase,
    masked_probs,
    neuralnet,
)
from rocalphago_tpu.models.policy import CNNPolicy, PolicyNet  # noqa: F401
from rocalphago_tpu.models.rollout import (  # noqa: F401
    ROLLOUT_FEATURES,
    CNNRollout,
    RolloutNet,
)
from rocalphago_tpu.models.seqpolicy import (  # noqa: F401
    SeqPolicy,
    SeqPolicyNet,
)
from rocalphago_tpu.models.value import CNNValue, ValueNet  # noqa: F401
