"""Crash-safe runtime layer shared by every trainer and CLI.

The seed stack assumed a perfect machine; long runs meet the opposite
(preempted workers, multi-hour ``nohup`` runs dying mid-write). This
package makes the harness survive the hardware (docs/RESILIENCE.md):

* :mod:`.atomic` — torn-write-proof artifact persistence
  (tmp + fsync + ``os.replace``);
* :mod:`.retries` — deterministic-jitter exponential backoff around
  device dispatch and checkpoint I/O, with a transient-vs-programming
  error classifier;
* :mod:`.faults` — opt-in deterministic fault injection at named
  barriers (``ROCALPHAGO_FAULT_PLAN=crash@iter3.post_save``), the
  mechanism the chaos tests use to prove exact resume;
* :mod:`.watchdog` — a heartbeat thread that logs ``stall`` events
  and can abort a hung run with a clean checkpoint;
* :mod:`.deadline` — hard wall-clock cutoffs for the serving path
  (the play-side enforcer behind the GTP engine's anytime genmove);
* :mod:`.pipeline` — pipelined chunk dispatch (keep a compiled chunk
  in flight while the host decides), the scheduling layer every
  chunked hot loop drives its per-chunk host decisions through;
* :mod:`.compilecache` — the one rule for where JAX's persistent
  compile cache lives (``JAX_COMPILATION_CACHE_DIR`` if set, else
  ``<checkout>/.jax_cache``), applied by every entry point.
"""

from rocalphago_tpu.runtime.compilecache import (  # noqa: F401
    enable_compile_cache,
)
from rocalphago_tpu.runtime.atomic import (  # noqa: F401
    atomic_write_bytes,
    atomic_write_json,
    atomic_write_text,
)
from rocalphago_tpu.runtime.deadline import Deadline  # noqa: F401
from rocalphago_tpu.runtime.faults import (  # noqa: F401
    FAULT_EXIT_CODE,
    FAULT_PLAN_ENV,
    InjectedFault,
    barrier,
)
from rocalphago_tpu.runtime.jsonl import (  # noqa: F401
    iter_jsonl,
    read_jsonl,
)
from rocalphago_tpu.runtime.pipeline import (  # noqa: F401
    ChunkPipeline,
    default_depth,
)
from rocalphago_tpu.runtime.retries import (  # noqa: F401
    donates,
    is_transient,
    retry,
    retry_call,
)
from rocalphago_tpu.runtime.watchdog import Watchdog  # noqa: F401
