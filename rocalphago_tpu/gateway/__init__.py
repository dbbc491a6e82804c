"""Network play gateway: the serve pool behind a wire.

Every entry point before this package was process-local — GTP over
stdin/stdout, :class:`~rocalphago_tpu.serve.sessions.ServePool` /
:class:`~rocalphago_tpu.multisize.pool.MultiSizePool` as in-process
Python APIs. The gateway turns the pool into an actual service:

* :mod:`~rocalphago_tpu.gateway.protocol` — the versioned NDJSON
  wire protocol (``new_game``/``play``/``genmove``/``close`` plus
  typed error codes, ``overload`` carrying a retry-after hint);
* :mod:`~rocalphago_tpu.gateway.server` — a threaded socket server
  mapping one connection to one pool session, with admission-backed
  connection caps (structured refusals, never hangs), per-request
  SLO deadlines, the resilience ladder per session, multi-size
  ``board`` routing, and a SIGTERM graceful drain;
* :mod:`~rocalphago_tpu.gateway.httpapi` — ``/healthz`` (the health
  JSON plus a ``"gateway"`` block) and ``/metrics`` (the obs
  registry's Prometheus rendering);
* :mod:`~rocalphago_tpu.gateway.client` — the client handle + load
  generator driving ``scripts/gateway_soak.py``.

Wire format, probe schema, drain semantics: docs/GATEWAY.md.
"""

from rocalphago_tpu.gateway.protocol import PROTO_VERSION  # noqa: F401
