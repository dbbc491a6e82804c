"""Closed-loop load for the gateway: a wire client and a generator.

Copied in shape from ``rocalphago_tpu/gateway/client.py``
(``GatewayClient``, ``run_load``: one connection per game, barrier
start) so that no later PR can change the yardstick by changing the
program; the original is listed in PERF.md §7 for deletion. It
imports nothing of the program and nothing of JAX: the rules engine
that deals legal prefixes and checks replies is handed in.

Closed loop: a client sends its next ``genmove`` only after the
previous reply, with no think time. Each genmove is timed by the
client, from just before the frame is written to just after the
reply is parsed.
"""

from __future__ import annotations

import contextlib
import json
import random
import socket
import threading
import time

#: GTP column letters (no I)
COLS = "ABCDEFGHJKLMNOPQRST"
#: bound on one reply line; the gateway's own default frame bound
MAX_FRAME = 65536


def to_vertex(move) -> str:
    return "pass" if move is None else f"{COLS[move[0]]}{move[1] + 1}"


def from_vertex(vertex: str):
    """``(x, y)``, None for pass; ValueError for anything else."""
    v = vertex.strip().upper()
    if v == "PASS":
        return None
    return COLS.index(v[0]), int(v[1:]) - 1


class WireError(Exception):
    """The connection dropped or answered out of protocol."""


class Client:
    """One NDJSON connection (= one server-side session slot)."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection((host, port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._reader = self.sock.makefile("rb")
        self._next_id = 0
        self.hello = self._recv()
        if self.hello.get("type") != "hello":
            self.close()
            raise WireError(f"no hello: {self.hello!r}")

    def _recv(self) -> dict:
        while True:
            line = self._reader.readline(MAX_FRAME + 1)
            if not line or not line.endswith(b"\n"):
                raise WireError("connection closed by gateway")
            if line.strip():
                return json.loads(line.decode("utf-8"))

    def request(self, msg: dict) -> dict:
        """Send one frame, return the reply with its id — a typed
        ``error`` frame is returned, not raised: the generator
        counts it."""
        self._next_id += 1
        msg = dict(msg, id=self._next_id)
        self.sock.sendall(
            (json.dumps(msg, sort_keys=True) + "\n").encode("utf-8"))
        reply = self._recv()
        if reply.get("id") != self._next_id:
            raise WireError(f"unexpected frame {reply!r}")
        return reply

    def new_game(self) -> dict:
        return self.request({"type": "new_game"})

    def play(self, color: str, vertex: str) -> dict:
        return self.request({"type": "play", "color": color,
                             "move": vertex})

    def genmove(self, color: str) -> dict:
        return self.request({"type": "genmove", "color": color})

    def close(self) -> None:
        for closer in (self._reader.close, self.sock.close):
            try:
                closer()
            except OSError:
                pass


def deal_prefix(new_state, length: int, rng: random.Random) -> list:
    """``length`` uniform random legal, non-eye-filling moves from
    the empty board, as ``(x, y)`` tuples (a pass where a side has no
    such move). Rejection sampling over empty points: one legality
    test per try, where listing every legal move costs 361."""
    state = new_state()
    size = state.size
    moves = []
    for _ in range(length):
        if state.is_end_of_game:
            break
        empties = [(x, y) for x in range(size) for y in range(size)
                   if state.board[x, y] == 0]
        rng.shuffle(empties)
        move = next((p for p in empties if state.is_legal(p)
                     and not state.is_eye(p, state.current_player)),
                    None)
        state.do_move(move)
        moves.append(move)
    return moves


class _Session:
    """One client's game: the connection and the mirror of the
    rules state."""

    def __init__(self, client: Client, new_state):
        self.client = client
        self.new_state = new_state
        self.state = None

    def open(self, prefix: list) -> None:
        self.state = self.new_state()
        reply = self.client.new_game()
        if reply.get("type") != "ok":
            raise WireError(f"new_game refused: {reply!r}")
        for move in prefix:
            color = "b" if self.state.current_player == 1 else "w"
            reply = self.client.play(color, to_vertex(move))
            if reply.get("type") != "ok":
                raise WireError(f"prefix move refused: {reply!r}")
            self.state.do_move(move)

    def genmove(self, span) -> tuple:
        """One timed genmove, checked: ``(latency_s, ok, why)``. A
        good reply is a ``move`` frame from rung ``search`` whose
        vertex is legal on the client's mirror of the game."""
        color = "b" if self.state.current_player == 1 else "w"
        t0 = time.monotonic()
        with span("chipbench.client_wait"):
            reply = self.client.genmove(color)
        dt = time.monotonic() - t0
        if reply.get("type") != "move":
            return dt, False, f"reply {reply.get('type')}:" \
                              f"{reply.get('code')}"
        if reply.get("rung") != "search":
            return dt, False, f"rung {reply.get('rung')}"
        try:
            move = from_vertex(str(reply.get("move", "")))
        except (ValueError, IndexError):
            return dt, False, f"vertex {reply.get('move')!r}"
        if move is not None and not self.state.is_legal(move):
            return dt, False, f"illegal {reply.get('move')}"
        self.state.do_move(move)
        return dt, True, ""


def closed_loop(host: str, port: int, clients: int, prefixes: list,
                new_state, seconds: float, span=None, on_start=None,
                timeout: float = 300.0) -> dict:
    """``clients`` closed-loop games against a gateway for
    ``seconds``.

    ``prefixes`` is a list of move lists; session ``i`` opens with
    ``prefixes[i]`` and takes the next unused one when its game ends.
    Every session first connects, plays its prefix and makes ONE
    untimed genmove (the ramp); a barrier then starts the window for
    all at once. A client sends no new request once ``seconds`` have
    passed; the window closes when the last reply is in, and every
    timed genmove counts. ``on_start`` runs once, after the ramp and
    before the window's clock starts (the traced run starts the
    profiler there).

    Returns ``{"started_at", "elapsed_s", "latencies_s", "attempted",
    "failed", "why": {reason: count}, "errors": [...]}``.
    """
    span = span or (lambda name: contextlib.nullcontext())
    t_start = [None]

    def stamp() -> None:
        # run by the last client to arrive, before any is released
        if on_start is not None:
            on_start()
        out["started_at"] = time.time()
        t_start[0] = time.monotonic()

    barrier = threading.Barrier(clients, action=stamp)
    lock = threading.Lock()
    spare = list(range(clients, len(prefixes)))
    out = {"latencies_s": [], "attempted": 0, "failed": 0, "why": {},
           "errors": []}
    ends = []

    def next_prefix() -> list:
        with lock:
            return prefixes[spare.pop(0)] if spare else prefixes[0]

    def worker(i: int) -> None:
        samples, end, err = [], None, None
        client = None
        try:
            client = Client(host, port, timeout=timeout)
            sess = _Session(client, new_state)
            sess.open(prefixes[i])
            sess.genmove(span)                      # ramp, untimed
            barrier.wait(timeout)
            t0 = t_start[0]
            while time.monotonic() - t0 < seconds:
                if sess.state.is_end_of_game:
                    sess.open(next_prefix())
                samples.append(sess.genmove(span))
            end = time.monotonic()
        except Exception as e:  # noqa: BLE001 — counted, load goes on
            err = f"client {i}: {type(e).__name__}: {e}"
            barrier.abort()
        finally:
            if client is not None:
                client.close()
        with lock:
            for dt, ok, why in samples:
                out["attempted"] += 1
                out["latencies_s"].append(dt)
                if not ok:
                    out["failed"] += 1
                    out["why"][why] = out["why"].get(why, 0) + 1
            if end is not None:
                ends.append(end)
            if err is not None:
                out["errors"].append(err)

    threads = [threading.Thread(target=worker, args=(i,),
                                name=f"chipbench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if out["errors"] or len(ends) != clients:
        raise WireError("; ".join(out["errors"]) or "a client died")
    out["elapsed_s"] = max(ends) - t_start[0]
    return out
