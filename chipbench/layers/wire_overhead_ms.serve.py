"""Gateway wire (``gateway/``, ``net/``): what the wire adds to a
genmove — the client's mean genmove time less the server's own
(growth of ``gateway_wire_seconds`` sum over growth of its count,
which times the handler's search and rules work alone). Ramp
genmoves are in the server's mean and not the client's; they are
one in some dozens."""

from chipbench.counters import histogram_delta


def read(ctx, raw):
    wire = histogram_delta(ctx.counters_before, ctx.counters_after,
                           "gateway_wire_seconds")
    lat = raw.get("latencies_s")
    if not wire or not wire[1] or not lat:
        return None
    return 1e3 * (sum(lat) / len(lat) - wire[0] / wire[1])
