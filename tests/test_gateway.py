"""Network play gateway (``rocalphago_tpu/gateway``): the NDJSON
wire protocol, structured shedding, per-request fault wall, drain
semantics, the HTTP probe sidecar, and the GTP bridge.

Fast tier: protocol framing unit tests (torn / oversized / undecodable
frames), a full happy-path conversation over a real socket, every
typed refusal (``bad_proto``, ``unknown_type``, ``no_game``,
``illegal_move``, ``bad_board``, ``overload`` at both the connection
cap and the pool's admission cap), abrupt-disconnect slot reclamation,
graceful drain (goodbye + clean thread exit + 503 health), multi-size
board routing, the ``--connect`` GTP bridge, and a short
``scripts/gateway_soak.py`` run in a subprocess. The multi-minute
default soak is ``slow``.
"""

import io
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from rocalphago_tpu.gateway import protocol
from rocalphago_tpu.gateway.client import (
    GatewayClient,
    GatewayClosed,
    GatewayError,
    GatewayRefused,
    connect_with_retry,
    run_load,
)
from rocalphago_tpu.gateway.server import GatewayServer
from rocalphago_tpu.io.metrics import MetricsLogger
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.runtime import faults
from rocalphago_tpu.runtime.jsonl import read_jsonl
from rocalphago_tpu.serve import ServePool

SIZE = 5
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    """Tests install plans programmatically; always restore the
    env-derived (empty) plan afterwards."""
    yield
    faults.install(None)


@pytest.fixture(scope="module")
def nets():
    from rocalphago_tpu.models import CNNPolicy, CNNValue

    pol = CNNPolicy(("board", "ones"), board=SIZE, layers=1,
                    filters_per_layer=2)
    val = CNNValue(("board", "ones", "color"), board=SIZE, layers=1,
                   filters_per_layer=2)
    return pol, val


@pytest.fixture(scope="module")
def pool(nets):
    """One warm 5×5 pool shared by the module (XLA compiles
    dominate); tests read stat DELTAS, never absolute counters."""
    pol, val = nets
    p = ServePool(val, pol, n_sim=6, max_sessions=4,
                  batch_sizes=(1, 2, 4), max_wait_us=2000)
    p.warm()
    yield p
    p.close()


@pytest.fixture(scope="module")
def server(pool):
    """One long-lived gateway for the happy-path / refusal tests.
    Shedding and drain tests build their own (drain is one-way).
    The SLO is armed but far off: the first genmove at a custom komi
    compiles the komi program (``pool.warm()`` does not), which under
    six test workers has taken over 2 s."""
    srv = GatewayServer(pool, max_conns=4, slo_ms=60000.0)
    srv.start()
    yield srv
    srv.close()


def settle(server, pool=None, timeout: float = 10.0) -> None:
    """Wait until the gateway's handler threads have released every
    connection slot (and, when given, the pool every session) — an
    abrupt client close is only *observed* by the server at its next
    read, so admission-sensitive asserts must not race it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        live = server.stats()["conns"]["live"]
        pool_live = (0 if pool is None
                     else pool.stats()["sessions"]["live"])
        if live == 0 and pool_live == 0:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"gateway did not settle: {server.stats()['conns']}")


def raw_conn(port: int):
    """A frame-level client: (socket, buffered reader) with the
    server's hello already consumed — for tests that must write
    malformed bytes no GatewayClient would ever send."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=30.0)
    reader = sock.makefile("rb")
    hello = protocol.read_frame(reader)
    assert hello["type"] == "hello"
    return sock, reader


# ----------------------------------------------------------- protocol


def test_frame_roundtrip_is_byte_stable():
    msg = {"type": "new_game", "id": 3, "board": 5, "komi": 5.5}
    wire = protocol.encode_frame(msg)
    assert wire.endswith(b"\n") and wire.count(b"\n") == 1
    # sorted keys: identical dicts encode identically
    assert wire == protocol.encode_frame(dict(reversed(msg.items())))
    assert protocol.read_frame(io.BytesIO(wire)) == msg


def test_torn_and_empty_frames_are_disconnects():
    assert protocol.read_frame(io.BytesIO(b"")) is None
    # EOF mid-line: a torn frame, not an error
    assert protocol.read_frame(io.BytesIO(b'{"type": "ok"')) is None
    assert protocol.read_frame(io.BytesIO(b"\n")) is None


def test_oversized_frame_is_fatal():
    big = b'{"pad": "' + b"x" * 100 + b'"}\n'
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.read_frame(io.BytesIO(big), limit=32)
    assert ei.value.code == "frame_too_big"
    assert ei.value.fatal


def test_frame_bound_counts_the_newline():
    ok = b'{"a": "' + b"x" * 22 + b'"}\n'      # exactly 32 bytes
    assert len(ok) == 32
    assert protocol.read_frame(io.BytesIO(ok), limit=32) is not None
    over = b'{"a": "' + b"x" * 23 + b'"}\n'    # 33 bytes, complete line
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.read_frame(io.BytesIO(over), limit=32)
    assert ei.value.code == "frame_too_big"
    assert ei.value.fatal


def test_blank_lines_are_skipped_not_disconnects():
    wire = b"\n\n" + protocol.encode_frame({"type": "ok"}) + b"\n"
    reader = io.BytesIO(wire)
    assert protocol.read_frame(reader) == {"type": "ok"}
    # the trailing blank line runs into EOF: a disconnect
    assert protocol.read_frame(reader) is None


def test_undecodable_frame_is_nonfatal():
    for bad in (b"{oops}\n", b"[1, 2]\n", b'"str"\n'):
        with pytest.raises(protocol.ProtocolError) as ei:
            protocol.read_frame(io.BytesIO(bad))
        assert ei.value.code == "bad_request"
        assert not ei.value.fatal


def test_error_frame_schema():
    f = protocol.error_frame("overload", "full", id=7,
                             retry_after_s=1.0)
    assert f == {"type": "error", "code": "overload", "msg": "full",
                 "id": 7, "retry_after_s": 1.0}
    with pytest.raises(AssertionError):
        protocol.error_frame("not_a_code", "nope")


# ----------------------------------------------------- happy path


def test_happy_path_conversation(server, pool):
    """hello → new_game → genmove/play/komi → close → new game on the
    SAME connection; probe counters move with the traffic."""
    before = server.stats()
    client = GatewayClient("127.0.0.1", server.port)
    try:
        assert client.hello["proto"] == protocol.PROTO_VERSION
        assert client.hello["name"] == "rocalphago-gateway"
        assert client.boards == (SIZE,)
        assert client.default_board == SIZE

        opened = client.new_game(komi=5.5)
        assert (opened["board"], opened["komi"]) == (SIZE, 5.5)

        reply = client.genmove("b")
        assert reply["type"] == "move"
        assert reply["elapsed_ms"] >= 0.0
        assert reply["slo_hit"] is False    # 60s SLO, 6-sim search
        assert "rung" in reply
        vertex = reply["move"]
        assert vertex == "pass" or vertex[0].isalpha()

        assert client.play("w", "pass")["type"] == "ok"
        assert client.set_komi(6.5)["type"] == "ok"
        assert client.close_game()["type"] == "ok"
        # the connection outlives the game: a second game opens
        assert client.new_game()["board"] == SIZE
    finally:
        client.close()
    settle(server, pool)
    after = server.stats()
    assert after["conns"]["accepted"] == before["conns"]["accepted"] + 1
    assert after["requests"]["genmoves"] \
        == before["requests"]["genmoves"] + 1
    assert after["requests"]["total"] >= before["requests"]["total"] + 6
    assert after["requests"]["unhandled"] \
        == before["requests"]["unhandled"]
    assert after["wire_ms"]["p50"] is not None
    assert after["slo_ms"] == 60000.0
    assert after["boards"] == [SIZE]


def test_hello_pins_protocol_version(server):
    client = GatewayClient("127.0.0.1", server.port)
    try:
        ok = client.request({"type": "hello",
                             "proto": protocol.PROTO_VERSION})
        assert ok["proto"] == protocol.PROTO_VERSION
        with pytest.raises(GatewayError) as ei:
            client.request({"type": "hello", "proto": 99})
        assert ei.value.code == "bad_proto"
    finally:
        client.close()
    settle(server)


# ------------------------------------------------------ typed refusals


def test_unknown_type_is_survivable(server):
    client = GatewayClient("127.0.0.1", server.port)
    try:
        with pytest.raises(GatewayError) as ei:
            client.request({"type": "flarb"})
        assert ei.value.code == "unknown_type"
        # the connection survived the refusal
        assert client.new_game()["type"] == "ok"
    finally:
        client.close()
    settle(server)


def test_requests_before_new_game_are_no_game(server):
    client = GatewayClient("127.0.0.1", server.port)
    try:
        for req in ({"type": "genmove", "color": "b"},
                    {"type": "play", "color": "b", "move": "C3"},
                    {"type": "komi", "komi": 7.5}):
            with pytest.raises(GatewayError) as ei:
                client.request(req)
            assert ei.value.code == "no_game"
    finally:
        client.close()
    settle(server)


def test_illegal_move_leaves_game_intact(server):
    client = GatewayClient("127.0.0.1", server.port)
    try:
        client.new_game()
        client.play("b", "C3")
        with pytest.raises(GatewayError) as ei:
            client.play("w", "C3")         # occupied point
        assert ei.value.code == "illegal_move"
        # state held: the game still answers
        assert client.genmove("w")["type"] == "move"
    finally:
        client.close()
    settle(server)


def test_bad_board_names_what_is_served(server):
    client = GatewayClient("127.0.0.1", server.port)
    try:
        with pytest.raises(GatewayError) as ei:
            client.new_game(board=9)
        assert ei.value.code == "bad_board"
        assert str(SIZE) in str(ei.value)
    finally:
        client.close()
    settle(server)


def test_malformed_new_game_fields_do_not_leak_sessions(server, pool):
    """A non-numeric ``komi``/``board`` is a typed ``bad_request``
    that never reaches the pool — repeated past ``max_sessions``
    it must not eat admission slots (the REVIEW.md leak)."""
    before = server.stats()
    client = GatewayClient("127.0.0.1", server.port)
    try:
        for _ in range(pool.stats()["sessions"]["max"] + 1):
            with pytest.raises(GatewayError) as ei:
                client.request({"type": "new_game", "komi": "abc"})
            assert ei.value.code == "bad_request"
        with pytest.raises(GatewayError) as ei:
            client.request({"type": "new_game", "komi": [6.5]})
        assert ei.value.code == "bad_request"
        with pytest.raises(GatewayError) as ei:
            client.request({"type": "new_game", "board": "five"})
        assert ei.value.code == "bad_request"
        assert pool.stats()["sessions"]["live"] == 0
        # every slot survived: a real game still opens
        assert client.new_game()["type"] == "ok"
    finally:
        client.close()
    settle(server, pool)
    after = server.stats()
    assert after["requests"]["unhandled"] \
        == before["requests"]["unhandled"]


def test_malformed_komi_is_bad_request_and_game_holds(server, pool):
    before = server.stats()["requests"]["unhandled"]
    client = GatewayClient("127.0.0.1", server.port)
    try:
        client.new_game()
        with pytest.raises(GatewayError) as ei:
            client.request({"type": "komi", "komi": {"k": 1}})
        assert ei.value.code == "bad_request"
        # the game survived the refusal
        assert client.genmove("b")["type"] == "move"
    finally:
        client.close()
    settle(server, pool)
    assert server.stats()["requests"]["unhandled"] == before


def test_blank_line_over_wire_is_harmless(server):
    sock, reader = raw_conn(server.port)
    try:
        sock.sendall(b"\n")
        sock.sendall(protocol.encode_frame(
            {"type": "hello", "id": 1,
             "proto": protocol.PROTO_VERSION}))
        assert protocol.read_frame(reader)["type"] == "ok"
    finally:
        reader.close()
        sock.close()
    settle(server)


def test_bad_json_over_wire_is_reported_not_fatal(server):
    sock, reader = raw_conn(server.port)
    try:
        sock.sendall(b"{this is not json\n")
        err = protocol.read_frame(reader)
        assert err["type"] == "error" and err["code"] == "bad_request"
        # the line boundary survived: the connection still works
        sock.sendall(protocol.encode_frame(
            {"type": "hello", "id": 1,
             "proto": protocol.PROTO_VERSION}))
        assert protocol.read_frame(reader)["type"] == "ok"
    finally:
        reader.close()
        sock.close()
    settle(server)


def test_oversized_frame_drops_the_connection(server):
    sock, reader = raw_conn(server.port)
    try:
        pad = "x" * (protocol.max_frame_bytes() + 16)
        sock.sendall(json.dumps({"pad": pad}).encode() + b"\n")
        err = protocol.read_frame(reader)
        assert err["code"] == "frame_too_big"
        # fatal: the server hangs up after the refusal
        assert protocol.read_frame(reader) is None
    finally:
        reader.close()
        sock.close()
    settle(server)


# --------------------------------------------------------- shedding


def test_connection_cap_sheds_with_retry_hint(pool):
    srv = GatewayServer(pool, max_conns=1).start()
    try:
        shed_c = obs_registry.counter("gateway_connections_total",
                                      result="shed")
        shed0 = shed_c.value
        first = GatewayClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(GatewayRefused) as ei:
                GatewayClient("127.0.0.1", srv.port)
            assert ei.value.code == "overload"
            assert ei.value.retry_after_s == 1.0
        finally:
            first.close()
        settle(srv)
        assert srv.stats()["conns"]["shed"] == 1
        assert shed_c.value == shed0 + 1
        # the slot came back: the next connection is admitted
        readmitted = GatewayClient("127.0.0.1", srv.port)
        readmitted.close()
        settle(srv)
        assert srv.stats()["conns"]["accepted"] == 2
    finally:
        srv.close()


def test_connect_with_retry_rides_out_a_shed(pool):
    """ISSUE 17 satellite: a client shed at accept backs off AT
    LEAST the server's ``retry_after_s`` (not just the jitter
    floor) and is admitted on a later attempt once a slot frees —
    the injectable sleep doubles as the slot-freeing hook, so the
    test asserts the schedule instead of waiting it out."""
    srv = GatewayServer(pool, max_conns=1).start()
    hog = GatewayClient("127.0.0.1", srv.port)
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        hog.close()
        settle(srv)

    try:
        c = connect_with_retry("127.0.0.1", srv.port, attempts=4,
                               base_delay=0.01, max_delay=0.05,
                               sleep=sleep)
        c.close()
        # exactly one shed round, floored by the refusal's hint
        # (jitter alone tops out at max_delay=0.05 here)
        assert len(sleeps) == 1 and sleeps[0] >= 1.0
        settle(srv)
        assert srv.stats()["conns"]["shed"] == 1
        assert srv.stats()["conns"]["accepted"] == 2
        # and a dead port still propagates the final failure
        with pytest.raises(OSError):
            connect_with_retry("127.0.0.1", 1, attempts=2,
                               base_delay=0.01, max_delay=0.02,
                               timeout=1.0, sleep=lambda s: None)
    finally:
        hog.close()
        srv.close()


def test_pool_admission_cap_sheds_new_game(pool):
    """More connections than pool sessions: the 5th new_game is a
    structured ``overload`` refusal from the pool's admission
    controller, not a hang — and closing a game frees the slot."""
    srv = GatewayServer(pool, max_conns=8).start()
    clients = []
    try:
        for _ in range(pool.stats()["sessions"]["max"]):
            c = GatewayClient("127.0.0.1", srv.port)
            clients.append(c)
            c.new_game()
        extra = GatewayClient("127.0.0.1", srv.port)
        clients.append(extra)
        with pytest.raises(GatewayRefused) as ei:
            extra.new_game()
        assert ei.value.code == "overload"
        assert ei.value.retry_after_s is not None
        assert srv.stats()["conns"]["shed"] >= 1
        clients[0].close_game()
        assert extra.new_game()["type"] == "ok"
    finally:
        for c in clients:
            c.close()
        settle(srv, pool)
        srv.close()


def test_abrupt_disconnect_reclaims_session_and_slot(server, pool):
    """A client that vanishes without ``close`` must not leak its
    pool session or its connection slot."""
    client = GatewayClient("127.0.0.1", server.port)
    client.new_game()
    assert pool.stats()["sessions"]["live"] >= 1
    client.sock.shutdown(socket.SHUT_RDWR)  # no goodbye, no close frame
    client.close()
    settle(server, pool)
    assert server.stats()["conns"]["live"] == 0


def test_load_generator_counts_partial_and_full_games(server):
    out = run_load("127.0.0.1", server.port, conns=2, moves=2,
                   board=SIZE)
    assert out["moves"] == 4
    assert out["sheds"] == out["disconnects"] == out["errors"] == 0
    assert len(out["latencies_s"]) == 4
    assert out["elapsed_s"] > 0
    settle(server)


# ------------------------------------------------------- fault wall


def test_injected_kill_aborts_connection_not_server(server, pool):
    """A kill at ``gateway.conn`` ends THAT connection with a typed
    ``internal`` error; the server keeps serving new ones."""
    before = server.stats()
    client = GatewayClient("127.0.0.1", server.port)
    faults.install("kill@gateway.conn:p=1.0,seed=3")
    try:
        with pytest.raises((GatewayError, GatewayClosed)) as ei:
            client.new_game()
        if isinstance(ei.value, GatewayError):
            assert ei.value.code == "internal"
    finally:
        faults.install(None)
        client.close()
    settle(server, pool)
    after = server.stats()
    assert after["faults"]["kills"] == before["faults"]["kills"] + 1
    assert after["requests"]["unhandled"] \
        == before["requests"]["unhandled"]
    # the server survived: a clean client plays on
    survivor = GatewayClient("127.0.0.1", server.port)
    try:
        assert survivor.new_game()["type"] == "ok"
    finally:
        survivor.close()
    settle(server, pool)


def test_injected_transient_fails_one_request_only(server, pool):
    """A transient at ``gateway.conn`` fails the request it hit and
    nothing else — the connection and its game survive."""
    before = server.stats()
    client = GatewayClient("127.0.0.1", server.port)
    try:
        client.new_game()
        faults.install("io_error@gateway.conn:p=1.0,seed=5")
        with pytest.raises(GatewayError) as ei:
            client.genmove("b")
        assert ei.value.code == "internal"
        faults.install(None)
        assert client.genmove("b")["type"] == "move"
    finally:
        faults.install(None)
        client.close()
    settle(server, pool)
    after = server.stats()
    assert after["faults"]["injected"] \
        == before["faults"]["injected"] + 1
    assert after["requests"]["unhandled"] \
        == before["requests"]["unhandled"]


# ------------------------------------------------------------- drain


def test_drain_is_graceful_idempotent_and_observable(pool, tmp_path):
    metrics = MetricsLogger(str(tmp_path / "metrics.jsonl"),
                            echo=False)
    srv = GatewayServer(pool, max_conns=4, metrics=metrics).start()
    from rocalphago_tpu.gateway.httpapi import GatewayHTTP

    http = GatewayHTTP(srv).start()
    client = GatewayClient("127.0.0.1", srv.port)
    client.new_game()
    try:
        srv.drain(reason="test")
        assert srv.draining
        # the idle connection was nudged out and its session closed
        settle(srv, pool)
        with pytest.raises(GatewayClosed):
            client.request({"type": "genmove", "color": "b"})
        # the listener is gone: new connections are refused at TCP
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", srv.port),
                                     timeout=2.0)
        # health flips to 503/draining for dumb LB checks
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "draining"
        srv.drain(reason="again")          # idempotent: returns fast
    finally:
        client.close()
        http.close()
        srv.close()
        metrics.close()
    phases = [r.get("phase") for r in
              read_jsonl(str(tmp_path / "metrics.jsonl"))
              if r.get("event") == "drain"]
    assert phases == ["gateway_requested", "gateway_accept_stopped",
                      "gateway_drained"]


# ------------------------------------------------------- HTTP probes


def test_healthz_and_metrics_endpoints(server, pool):
    from rocalphago_tpu.gateway.httpapi import GatewayHTTP

    http = GatewayHTTP(server).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/healthz",
                timeout=10) as r:
            assert r.status == 200
            body = json.loads(r.read())
        assert body["status"] == "ok"
        assert body["serve"]["sessions"]["max"] \
            == pool.stats()["sessions"]["max"]
        assert body["gateway"]["proto"] == protocol.PROTO_VERSION
        assert set(body["gateway"]["conns"]) \
            == {"live", "max", "accepted", "shed"}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/metrics",
                timeout=10) as r:
            text = r.read().decode()
        assert "gateway_conns_live" in text
        assert 'gateway_connections_total{result="accepted"}' in text
        assert "gateway_wire_seconds" in text
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        http.close()


# -------------------------------------------------- multi-size routing


def test_multisize_pool_routes_by_board(nets):
    from rocalphago_tpu.multisize import MultiSizePool

    pol, val = nets
    mpool = MultiSizePool(val, pol, sizes=(5, 7), n_sim=4,
                          batch_sizes=(1, 2))
    srv = GatewayServer(mpool, max_conns=2).start()
    try:
        client = GatewayClient("127.0.0.1", srv.port)
        try:
            assert client.boards == (5, 7)
            assert client.default_board == 5
            assert client.new_game(board=7)["board"] == 7
            assert client.genmove("b")["type"] == "move"
            with pytest.raises(GatewayError) as ei:
                client.new_game(board=9)
            assert ei.value.code == "bad_board"
        finally:
            client.close()
        settle(srv)
    finally:
        srv.close()
        mpool.close()


# -------------------------------------------------------- GTP bridge


def test_gtp_bridge_speaks_gtp_over_the_wire(server):
    from rocalphago_tpu.interface.gtp import GatewayBridge

    client = GatewayClient("127.0.0.1", server.port)
    bridge = GatewayBridge(client)
    try:
        assert bridge.handle("protocol_version") == ("= 2\n\n", False)
        assert bridge.handle("name") \
            == ("= rocalphago-gateway\n\n", False)
        assert bridge.handle("known_command genmove") \
            == ("= true\n\n", False)
        assert bridge.handle(f"boardsize {SIZE}") == ("=\n\n", False)
        reply, done = bridge.handle("boardsize 19")
        assert reply == "? unacceptable size\n\n" and not done
        assert bridge.handle("clear_board") == ("=\n\n", False)
        assert bridge.handle("komi 6.5") == ("=\n\n", False)
        reply, done = bridge.handle("genmove b")
        assert reply.startswith("= ") and not done
        assert bridge.handle("play w pass") == ("=\n\n", False)
        reply, done = bridge.handle("frobnicate")
        assert reply == "? unknown command\n\n" and not done
        reply, done = bridge.handle("1 quit")
        assert reply == "=1\n\n" and done
    finally:
        client.close()
    settle(server)


def test_gtp_bridge_loop_and_shed_reporting(server, pool):
    from rocalphago_tpu.interface.gtp import (
        GatewayBridge,
        run_bridge,
    )

    client = GatewayClient("127.0.0.1", server.port)
    out = io.StringIO()
    try:
        run_bridge(GatewayBridge(client),
                   instream=io.StringIO(
                       "name\ngenmove b\nquit\nname\n"),
                   outstream=out)
    finally:
        client.close()
    text = out.getvalue()
    # the loop stopped at quit: exactly one name reply
    assert text.count("= rocalphago-gateway") == 1
    assert "= " in text.split("rocalphago-gateway")[1]
    settle(server, pool)


def test_gtp_connect_cli_reports_refusal(pool):
    """``gtp.py --connect`` against a full gateway exits with the
    structured refusal, not a traceback or a hang."""
    from rocalphago_tpu.interface import gtp

    srv = GatewayServer(pool, max_conns=1).start()
    holder = GatewayClient("127.0.0.1", srv.port)
    try:
        with pytest.raises(SystemExit) as ei:
            gtp.main(["--connect", f"127.0.0.1:{srv.port}"])
        assert "gateway refused" in str(ei.value)
        assert "retry" in str(ei.value)
    finally:
        holder.close()
        settle(srv)
        srv.close()
    # malformed --connect is an argparse error, before any network
    with pytest.raises(SystemExit):
        gtp.main(["--connect", "no-port-here"])


# --------------------------------------------------------------- soak


def run_soak(tmp_path, extra):
    out_dir = str(tmp_path / "soak")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "gateway_soak.py"),
         "--out", out_dir, *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=600)
    return proc, os.path.join(out_dir, "summary.json")


def check_soak(proc, out):
    assert proc.returncode == 0, \
        f"soak failed:\n{proc.stdout}\n{proc.stderr}"
    with open(out) as f:
        summary = json.load(f)
    assert all(summary["checks"].values()), summary["checks"]
    assert summary["unhandled"] == 0
    assert summary["sheds_metrics"] == summary["sheds_server"] > 0
    return summary


@pytest.mark.slow
def test_gateway_soak_smoke(tmp_path):
    """The chaos soak, sized for the full tier (suite wall-time): kills at the
    connection barrier, sheds counted in /metrics, a green gate
    after the storm, and a clean SIGTERM drain (exit 0)."""
    proc, out = run_soak(tmp_path, ["--conns", "3", "--max-conns", "2",
                                    "--moves", "3", "--min-kills", "1",
                                    "--p-kill", "0.3",
                                    "--deadline-s", "150"])
    summary = check_soak(proc, out)
    assert summary["kills"] >= 1


@pytest.mark.slow
def test_gateway_soak_full(tmp_path):
    proc, out = run_soak(tmp_path, [])
    summary = check_soak(proc, out)
    assert summary["kills"] >= 3
