"""The shared wire core (``rocalphago_tpu.net``): framing, backoff,
line-server admission/drain.

Tier-1 units for the layer PR 15's gateway proved under chaos and
PR 17 factored out so replaynet speaks it byte-for-byte: the NDJSON
reader rules (frame bound, torn tail, blank-line keepalives,
undecodable lines), the deterministic-jitter retry loop with the
server's ``retry_after_s`` as a sleep floor, and the
:class:`LineServerCore` accept/shed/drain machinery against a real
socket. All jax-free.
"""

import io
import json
import socket
import threading

import pytest

from rocalphago_tpu.net import protocol
from rocalphago_tpu.net.client import call_with_backoff, default_transient
from rocalphago_tpu.net.server import LineServerCore

# ---------------------------------------------------------- framing


def reader_of(raw: bytes):
    return io.BufferedReader(io.BytesIO(raw))


def test_encode_frame_is_sorted_and_newline_terminated():
    raw = protocol.encode_frame({"b": 1, "a": 2})
    assert raw == b'{"a": 2, "b": 1}\n'
    assert protocol.read_frame(reader_of(raw), 1024) == {"a": 2,
                                                        "b": 1}


def test_read_frame_skips_blank_lines_and_ends_on_eof():
    r = reader_of(b"\n\n" + protocol.encode_frame({"x": 1}))
    assert protocol.read_frame(r, 1024) == {"x": 1}
    assert protocol.read_frame(r, 1024) is None  # clean EOF


def test_read_frame_torn_tail_is_a_disconnect_not_an_error():
    assert protocol.read_frame(reader_of(b'{"x": 1'), 1024) is None


def test_read_frame_over_limit_is_fatal():
    raw = protocol.encode_frame({"pad": "y" * 100})
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.read_frame(reader_of(raw), 32)
    assert ei.value.code == "frame_too_big"
    assert ei.value.fatal


def test_read_frame_bad_json_is_nonfatal_and_reader_continues():
    r = reader_of(b"not json\n" + protocol.encode_frame({"k": 1}))
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.read_frame(r, 1024)
    assert ei.value.code == "bad_request"
    assert not ei.value.fatal
    # the line boundary survived: the next frame reads fine
    assert protocol.read_frame(r, 1024) == {"k": 1}


def test_read_frame_non_object_is_bad_request():
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.read_frame(reader_of(b"[1, 2]\n"), 1024)
    assert ei.value.code == "bad_request"


def test_error_frame_vocabulary_is_enforced():
    codes = ("overload", "draining")
    f = protocol.error_frame("overload", "full", id=7,
                             retry_after_s=1.23456, codes=codes)
    assert f == {"type": "error", "code": "overload", "msg": "full",
                 "id": 7, "retry_after_s": 1.235}
    with pytest.raises(AssertionError):
        protocol.error_frame("overlaod", "typo", codes=codes)


# ---------------------------------------------------------- backoff


class _Refused(Exception):
    def __init__(self, retry_after_s=None):
        super().__init__("refused")
        self.retry_after_s = retry_after_s


def test_default_transient_classes():
    class SomethingClosed(Exception):
        pass

    class Shed(Exception):
        retry_after_s = None

    assert default_transient(OSError("gone"))
    assert default_transient(ConnectionResetError())
    assert default_transient(_Refused(retry_after_s=2.0))
    assert default_transient(SomethingClosed())
    # the *Refused/*Closed family is transient BY NAME, hint or not
    assert default_transient(_Refused(retry_after_s=None))
    assert not default_transient(ValueError("typo"))
    assert not default_transient(Shed())


def test_backoff_retries_transients_and_honors_retry_after():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise _Refused(retry_after_s=1.5)
        return "ok"

    out = call_with_backoff(flaky, attempts=6, base_delay=0.01,
                            max_delay=0.05, seed=3,
                            sleep=sleeps.append)
    assert out == "ok" and calls["n"] == 4
    # the server's hint floors every sleep: jitter alone would be
    # well under 0.05s here
    assert len(sleeps) == 3 and all(s >= 1.5 for s in sleeps)


def test_backoff_schedule_is_deterministic():
    def run():
        sleeps = []
        tries = {"n": 0}

        def fn():
            tries["n"] += 1
            if tries["n"] < 4:
                raise OSError("drop")
            return tries["n"]

        call_with_backoff(fn, attempts=5, base_delay=0.25,
                          max_delay=5.0, seed=11, key="t",
                          sleep=sleeps.append)
        return sleeps

    a, b = run(), run()
    assert a == b and len(a) == 3
    assert a[0] < a[-1]            # exponential-ish growth


def test_backoff_raises_nontransient_immediately():
    calls = {"n": 0}

    def typo():
        calls["n"] += 1
        raise ValueError("bug")

    with pytest.raises(ValueError):
        call_with_backoff(typo, attempts=6, sleep=lambda s: None)
    assert calls["n"] == 1


def test_backoff_budget_exhaustion_raises_last_exception():
    def always():
        raise OSError("still down")

    with pytest.raises(OSError):
        call_with_backoff(always, attempts=3, base_delay=0.001,
                          max_delay=0.002, sleep=lambda s: None)
    with pytest.raises(ValueError):
        call_with_backoff(lambda: 1, attempts=0)


# ------------------------------------------------------ server core


class _Log:
    def __init__(self):
        self.events = []

    def log(self, event, **fields):
        self.events.append(dict(fields, event=event))


def echo_core(max_conns=4, drain_s=2.0, metrics=None):
    """A minimal echo server on the core: hello first, then every
    frame comes back with ``echoed: true``."""
    core = {}

    def handler(conn, reader, cid):
        core["c"].send(conn, {"type": "hello", "cid": cid})
        while True:
            if core["c"].draining:
                return
            msg = protocol.read_frame(reader, 4096)
            if msg is None:
                return
            core["c"].send(conn, dict(msg, echoed=True))

    def refusal(code):
        return {"type": "error", "code": code, "retry_after_s": 1.0}

    core["c"] = LineServerCore(max_conns=max_conns, drain_s=drain_s,
                               handler=handler, refusal=refusal,
                               name="echo", metrics=metrics)
    return core["c"].start()


def wire(port):
    s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    return s, s.makefile("rb")


def test_core_serves_and_echoes():
    core = echo_core()
    try:
        s, r = wire(core.port)
        assert protocol.read_frame(r, 4096)["type"] == "hello"
        s.sendall(protocol.encode_frame({"type": "ping", "n": 1}))
        assert protocol.read_frame(r, 4096) == {"type": "ping",
                                                "n": 1,
                                                "echoed": True}
        s.close()
        assert core.counters()["accepted"] == 1
    finally:
        core.drain()


def test_core_sheds_over_cap_with_typed_refusal():
    core = echo_core(max_conns=1)
    socks = []
    try:
        s1, r1 = wire(core.port)
        socks.append(s1)
        assert protocol.read_frame(r1, 4096)["type"] == "hello"
        s2, r2 = wire(core.port)
        socks.append(s2)
        refusal = protocol.read_frame(r2, 4096)
        assert refusal["code"] == "overload"
        assert refusal["retry_after_s"] == 1.0
        # the shed socket closes; the admitted one still answers
        assert protocol.read_frame(r2, 4096) is None
        s1.sendall(protocol.encode_frame({"type": "ping"}))
        assert protocol.read_frame(r1, 4096)["echoed"]
        c = core.counters()
        assert c["accepted"] == 1 and c["shed"] == 1
    finally:
        for s in socks:
            s.close()
        core.drain()


def test_core_drain_quiesces_and_emits_phases():
    log = _Log()
    core = echo_core(metrics=log)
    s, r = wire(core.port)
    assert protocol.read_frame(r, 4096)["type"] == "hello"
    t = threading.Thread(target=core.drain, args=("test",))
    t.start()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert core.draining
    phases = [e["phase"] for e in log.events if e["event"] == "drain"]
    assert phases == ["echo_requested", "echo_accept_stopped",
                      "echo_drained"]
    assert core.counters()["live"] == 0
    # port survives drain (the listener socket is closed first)
    assert isinstance(core.port, int)
    # a late connect is refused at the socket level, never hangs
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", core.port),
                                 timeout=0.5)
    s.close()
    core.drain()   # idempotent
    assert phases == [e["phase"] for e in log.events
                      if e["event"] == "drain"]


def test_core_send_reports_dead_peer():
    core = echo_core()
    try:
        s, r = wire(core.port)
        protocol.read_frame(r, 4096)
        s.close()
        r.close()
        dead = socket.socket()
        dead.close()
        assert core.send(dead, {"type": "x"}) is False
    finally:
        core.drain()
