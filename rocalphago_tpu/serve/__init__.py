"""Fleet-grade play service: one fused evaluator, many live games.

The path from "one GTP process per game" to heavy-traffic serving is
throughput-by-batching: every active search is blocked on the same
tiny policy+value forward, so pending leaf evaluations from ALL live
games coalesce into one device batch (the economics behind Pgx's
10^4–10^6 steps/s band and KataGo's batched self-play service —
PAPERS.md). The subsystem fuses pieces that already exist:

* :mod:`.evaluator` — the shared :class:`~.evaluator.
  BatchingEvaluator`: one jit-compiled policy+value program at a few
  fixed batch sizes, fed by a queue that coalesces pending leaf-eval
  requests across sessions under a fill-target / max-wait-µs dispatch
  policy, padding to the nearest compiled size;
* :mod:`.sessions` — :class:`~.sessions.ServePool` /
  :class:`~.sessions.SessionPlayer`: N concurrent game sessions
  sharing ONE compiled search (``search/device_mcts.py``'s
  ``prepare_sim``/``apply_sim`` seam) whose leaf evaluations go
  through the shared evaluator instead of each session's own jit
  program;
* :mod:`.admission` — bounded queue + session caps; under overload a
  shed (:class:`~.admission.EvaluatorOverload`) steps the session
  down the existing :class:`~rocalphago_tpu.interface.resilient.
  ResilientPlayer` ladder (reduced sims → raw policy → rules
  fallback) and the :class:`~rocalphago_tpu.runtime.deadline.
  Deadline` SLO guarantees an anytime answer.

Architecture, dispatch policy and knobs: docs/SERVING.md. On the chip
the pool has been driven by hand only (``chipbench/drivers/
serve_closed.py``, PERF.md §5); no benchmark cell times it yet.
"""

from rocalphago_tpu.serve.admission import (  # noqa: F401
    AdmissionController,
    AdmissionError,
    EvaluatorOverload,
)
from rocalphago_tpu.serve.evaluator import BatchingEvaluator  # noqa: F401
from rocalphago_tpu.serve.sessions import (  # noqa: F401
    FleetDriver,
    ServePool,
    ServeSession,
    SessionPlayer,
)
