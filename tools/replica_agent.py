"""Replica agent: host one serve replica behind a TCP port
(docs/serving.md "Cross-host fleet").

The cross-host counterpart of the stdio replica worker: one agent per
host leases out ONE replica slot, speaking the same hardened frame
codec (``serve/frames.py``) and running the same
:class:`~bigdl_tpu.serve.cluster.WorkerOps` op set the subprocess
workers run — engine, decode, or prefill role, chosen by the client's
init frame.  ``python -m tools.replica_agent --port 7070`` on each
host, then ``BIGDL_SERVE_HOSTS=h1:7070,h2:7070`` on the pool side.

Session protocol (what TCP adds over a pipe):

- **hello/welcome handshake**: the first client bytes are a ``hello``
  in a FIXED pickle-free layout (``frames.read_hello`` — the op
  frames are pickle, and unpickling an unauthenticated peer's bytes
  would be remote code execution, so nothing is deserialized before
  the shared token (``BIGDL_SERVE_TOKEN``, compared constant-time)
  checks out).  A null session id opens a fresh session (superseding
  any previous one — an agent is one replica slot), a non-null one
  re-attaches after a blip.  The ``welcome`` carries the session id +
  epoch; a bad token or unknown session gets a typed refusal and a
  closed connection.  The agent binds 127.0.0.1 by default and
  REFUSES to listen on a non-loopback interface with an empty token.
- **sequenced outbox**: every session frame the agent sends (ready,
  events, token chunks, replies) carries a contiguous ``seq`` and is
  retained until the client acks it (the ``acked`` watermark
  piggybacked on hello/ping frames).  A re-attach replays everything
  un-acked, in order — the client dedups by ``seq``, so a reply the
  blip swallowed is re-delivered exactly once.
- **request dedup**: the client replays its un-answered requests on
  re-attach; the agent drops request ids it already executed, so a
  request is never run twice no matter where the cut fell.
- **liveness**: a session whose connection stays gone past
  ``BIGDL_SERVE_SESSION_TTL_S`` (default 30) is reaped — its replica
  closed, its host lease effectively returned.

Chaos: ``BIGDL_FAULTS=serve_partition@at=N[,len_s=S]`` black-holes the
agent at the Nth submit — the triggering request is processed FIRST
(its reply waits in the outbox), then the connection drops and new
connections are refused for S seconds.  A blip under the client's
liveness budget must re-attach with zero requeues; a longer one
converts to the normal death path.  ``serve_kill`` works here too
(``os._exit`` inside the shared WorkerOps) and kills the whole agent —
real death, not a blip.
"""
from __future__ import annotations

import argparse
import hmac
import itertools
import os
import pickle
import socket
import struct
import sys
import threading
import time
from collections import deque

from bigdl_tpu.serve.frames import (FrameProtocolError, read_frame,
                                    read_hello, write_frame,
                                    write_refusal, write_welcome)

ENV_SESSION_TTL = "BIGDL_SERVE_SESSION_TTL_S"
DEFAULT_SESSION_TTL_S = 30.0
ENV_TOKEN = "BIGDL_SERVE_TOKEN"


def _loopback(host: str) -> bool:
    return host in ("localhost", "::1", "") or host.startswith("127.")


def session_ttl_default() -> float:
    try:
        return float(os.environ.get(ENV_SESSION_TTL, "")
                     or DEFAULT_SESSION_TTL_S)
    except ValueError:
        return DEFAULT_SESSION_TTL_S


class _PartitionDrop(Exception):
    """Internal: unwind a connection for the serve_partition chaos
    site (the session survives; the socket does not)."""


class _Conn:
    __slots__ = ("sock", "rfile", "wfile")

    def __init__(self, sock):
        self.sock = sock
        self.rfile = sock.makefile("rb")
        self.wfile = sock.makefile("wb")

    def close(self):
        for f in (self.wfile, self.rfile):
            try:
                f.close()
            except (OSError, ValueError):
                pass
        try:
            self.sock.close()
        except OSError:
            pass


class Session:
    """One client's replica slot: the ops handler plus the sequenced
    replay outbox that makes a re-attach lossless.  ``send`` is handed
    to WorkerOps as its reply channel — every outbound frame gets a
    ``seq``, lands in the outbox, and goes out on whatever connection
    is currently attached (write failures are silently absorbed: the
    frame replays on the next attach)."""

    def __init__(self, sid: str, epoch: int):
        self.sid = sid
        self.epoch = epoch
        #: one lock serializes seq assignment AND the socket writes, so
        #: frames leave in seq order even when an attach's replay races
        #: a live reply callback
        self.lock = threading.RLock()
        self.next_seq = 1
        self.outbox = deque()       # (seq, frame), pruned by client acks
        #: executed request ids (replay dedup).  Grows with request
        #: count — acceptable for a slot that lives as long as one
        #: replica lease.  Pings are exempt (idempotent, never
        #: replayed), so the keepalive cadence does not leak into it
        self.seen_rids = set()
        self.ops = None
        self.conn = None
        self.detached_at = time.monotonic()
        self.closed = False

    def send(self, msg):
        with self.lock:
            if self.closed:
                return
            msg = dict(msg)
            msg["seq"] = self.next_seq
            self.next_seq += 1
            self.outbox.append((msg["seq"], msg))
            if self.conn is not None:
                try:
                    write_frame(self.conn.wfile, msg)
                except Exception:
                    # a dying connection mid-write: detach, replay later
                    self.conn = None
                    self.detached_at = time.monotonic()

    def ack(self, acked: int):
        with self.lock:
            while self.outbox and self.outbox[0][0] <= acked:
                self.outbox.popleft()

    def attach(self, conn, acked: int):
        """Install a (re)connected socket and replay the un-acked
        outbox in order.  Raises on a write failure — the caller drops
        the connection and the client retries."""
        with self.lock:
            self.ack(acked)
            self.conn = conn
            self.detached_at = None
            for _, msg in list(self.outbox):
                write_frame(conn.wfile, msg)

    def detach(self, conn):
        with self.lock:
            if self.conn is conn:
                self.conn = None
                self.detached_at = time.monotonic()

    def close(self):
        with self.lock:
            if self.closed:
                return
            self.closed = True
            self.conn = None
        if self.ops is not None:
            try:
                self.ops.close_abrupt()
            except Exception:   # pragma: no cover - replica teardown
                pass


class ReplicaAgent:
    """The TCP listener.  Usable in-process (tests:
    ``ReplicaAgent(port=0).start()`` on a loopback ephemeral port) or
    as a standalone process via :func:`main`."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 token=None, session_ttl_s: float | None = None,
                 once: bool = False, forward_events: bool = False):
        from bigdl_tpu.serve import remote as remote_mod
        self.host = host
        self.port = int(port)
        self.token = (token if token is not None
                      else remote_mod.token_default())
        self.session_ttl_s = (session_ttl_default() if session_ttl_s is None
                              else float(session_ttl_s))
        self.once = once
        self.forward_events = forward_events
        self._sessions: dict = {}
        self._seq = itertools.count(1)
        self._lock = threading.Lock()
        self._blackhole_until = 0.0
        self._closed = threading.Event()
        self.done = threading.Event()
        self._sock = None

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if not self.token and not _loopback(self.host):
            raise ValueError(
                f"refusing to listen on non-loopback {self.host!r} "
                f"with an empty token: any peer that can reach the "
                f"port could lease the replica slot.  Set {ENV_TOKEN} "
                f"(or --token), or bind 127.0.0.1")
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((self.host, self.port))
        sock.listen(16)
        self.port = sock.getsockname()[1]
        self._sock = sock
        threading.Thread(target=self._accept_loop, daemon=True,
                         name=f"bigdl-agent-{self.port}-accept").start()
        threading.Thread(target=self._reap_loop, daemon=True,
                         name=f"bigdl-agent-{self.port}-reaper").start()
        if self.forward_events:
            # stream this process's obs events to the attached client
            # (the ProcessReplica `op: event` contract over TCP); only
            # the standalone agent does this — an in-process agent's
            # events already live in the host log
            from bigdl_tpu.obs import events as obs_events
            log = obs_events.get()
            if log is not None:
                log.add_sink(self._forward_event)
        return self

    def close(self):
        if self._closed.is_set():
            return
        self._closed.set()
        try:
            if self._sock is not None:
                self._sock.close()
        except OSError:   # pragma: no cover - teardown
            pass
        with self._lock:
            sessions = list(self._sessions.values())
            self._sessions.clear()
        for s in sessions:
            s.close()
        self.done.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- event forwarding (standalone agents) -------------------------------
    def _forward_event(self, ev):
        with self._lock:
            sessions = list(self._sessions.values())
        for s in sessions:
            s.send({"op": "event", "event": ev})

    # -- accept / handshake -------------------------------------------------
    def _accept_loop(self):
        while not self._closed.is_set():
            try:
                sock, _addr = self._sock.accept()
            except OSError:
                return
            if time.monotonic() < self._blackhole_until:
                # partitioned: the network "drops" every packet — a new
                # connection attempt just dies
                try:
                    sock.close()
                except OSError:
                    pass
                continue
            threading.Thread(
                target=self._serve_conn, args=(sock,), daemon=True,
                name=f"bigdl-agent-{self.port}-conn").start()

    def _serve_conn(self, sock):
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # bounded sends: Session.send/attach write while holding
        # session.lock, and a black-holed peer (packets dropped, no
        # RST) would otherwise block a full kernel send buffer for the
        # TCP timeout — stalling rid dedup, close() and the TTL reaper
        # behind that lock.  A timed-out write just detaches this
        # connection; the frame replays on the next attach.
        send_s = max(1.0, min(10.0, self.session_ttl_s / 4.0))
        sock.setsockopt(
            socket.SOL_SOCKET, socket.SO_SNDTIMEO,
            struct.pack("ll", int(send_s), int((send_s % 1.0) * 1e6)))
        conn = _Conn(sock)
        session = None
        try:
            session = self._handshake(conn)
            if session is None:
                return
            self._read_loop(session, conn)
        except _PartitionDrop:
            pass
        except FrameProtocolError as e:
            # garbage/corrupt/oversized bytes never reach pickle: name
            # the violation on the ring and drop the connection
            print(f"agent {self.host}:{self.port}: frame protocol "
                  f"violation: {e}; dropping connection",
                  file=sys.stderr, flush=True)
        except (OSError, ValueError, EOFError, pickle.PickleError):
            pass
        finally:
            if session is not None:
                session.detach(conn)
            conn.close()

    def _handshake(self, conn):
        """Authenticate BEFORE deserializing anything: the hello is a
        fixed pickle-free layout (``frames.read_hello``), so an
        unauthenticated peer's bytes never reach ``pickle.loads`` —
        garbage fails typed on magic/version/field bounds, and only a
        token-bearing client gets the pickled op stream."""
        hello = read_hello(conn.rfile)
        if hello is None:
            return None
        if not hmac.compare_digest(
                str(hello.get("token") or "").encode("utf-8"),
                str(self.token or "").encode("utf-8")):
            print(f"agent {self.host}:{self.port}: rejected connection "
                  f"(bad token)", file=sys.stderr, flush=True)
            write_refusal(conn.wfile, "bad token: agent and client "
                          "must share BIGDL_SERVE_TOKEN")
            return None
        sid = hello.get("session")
        if sid is None:
            session = self._new_session()
            resumed = False
        else:
            with self._lock:
                session = self._sessions.get(sid)
            if session is None or session.closed:
                write_refusal(
                    conn.wfile,
                    f"unknown session {sid!r}: agent restarted "
                    f"or the session expired "
                    f"({ENV_SESSION_TTL}={self.session_ttl_s})")
                return None
            resumed = True
        write_welcome(conn.wfile, session.sid, session.epoch, resumed,
                      os.getpid())
        session.attach(conn, int(hello.get("acked") or 0))
        return session

    def _new_session(self) -> Session:
        n = next(self._seq)
        session = Session(f"s{n}", epoch=n)
        with self._lock:
            # ONE replica slot per agent: a fresh hello supersedes any
            # previous session (its replica is torn down, the host is
            # re-leasable)
            old = list(self._sessions.values())
            self._sessions = {session.sid: session}
        for s in old:
            s.close()
        return session

    # -- op loop ------------------------------------------------------------
    def _read_loop(self, session, conn):
        from bigdl_tpu.resilience import faults
        from bigdl_tpu.serve import cluster
        injector = faults.get()
        while not self._closed.is_set():
            msg = read_frame(conn.rfile)
            if msg is None:
                return
            if not isinstance(msg, dict):
                continue
            if "acked" in msg:
                session.ack(int(msg["acked"]))
            op = msg.get("op")
            if op in ("hello", "ack"):
                continue
            rid = msg.get("id")
            if rid is not None and op != "ping":
                # pings skip the dedup set: they are idempotent and the
                # client never replays them, and at the liveness/4
                # cadence they would otherwise leak an rid entry every
                # ~0.5s for the whole session lifetime
                with session.lock:
                    if rid in session.seen_rids:
                        # a replayed request this slot already executed:
                        # its reply is (or will be) in the outbox
                        continue
                    session.seen_rids.add(rid)
            if op == "init":
                if session.ops is None:
                    session.ops = cluster.build_worker_ops(
                        msg, session.send)
                    session.send({"op": "ready", "pid": os.getpid()})
                continue
            if session.ops is None:
                session.send({"id": rid, "ok": False,
                              "etype": "RuntimeError",
                              "error": "no init frame yet"})
                continue
            if (op == "submit" and injector is not None
                    and injector.armed("serve_partition")):
                spec = injector.fires("serve_partition")
                if spec is not None:
                    # the triggering request is processed FIRST — its
                    # reply/chunks land in the outbox, so a re-attach
                    # inside the liveness budget replays them and the
                    # blip costs zero requeues
                    session.ops.handle(msg)
                    self._partition(spec.len_s)
            if not session.ops.handle(msg):
                self._end_session(session)
                return

    def _partition(self, len_s: float):
        from bigdl_tpu.obs import events as obs_events
        print(f"serve_partition chaos fired: black-holing agent "
              f"{self.host}:{self.port} for {len_s}s",
              file=sys.stderr, flush=True)
        obs_events.emit("remote", kind="partition", len_s=float(len_s))
        self._blackhole_until = time.monotonic() + float(len_s)
        raise _PartitionDrop()

    def _end_session(self, session):
        with self._lock:
            self._sessions.pop(session.sid, None)
        session.close()
        if self.once:
            self.close()

    # -- session TTL reaper -------------------------------------------------
    def _reap_loop(self):
        period = max(0.05, min(1.0, self.session_ttl_s / 4.0))
        while not self._closed.wait(period):
            now = time.monotonic()
            stale = []
            with self._lock:
                for sid, s in list(self._sessions.items()):
                    da = s.detached_at
                    if da is not None and now - da > self.session_ttl_s:
                        stale.append(s)
                        self._sessions.pop(sid, None)
            for s in stale:
                print(f"agent {self.host}:{self.port}: session {s.sid} "
                      f"detached > {self.session_ttl_s}s; reaping",
                      file=sys.stderr, flush=True)
                s.close()
                if self.once:
                    self.close()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="bigdl_tpu replica agent: lease this host's "
                    "replica slot over TCP")
    parser.add_argument("--host", default="127.0.0.1",
                        help="bind interface (default loopback; a "
                             "non-loopback bind requires a token)")
    parser.add_argument("--port", type=int, default=0,
                        help="0 = ephemeral (printed as AGENT_PORT=)")
    parser.add_argument("--token", default=None,
                        help="shared handshake secret (default: "
                             "BIGDL_SERVE_TOKEN)")
    parser.add_argument("--once", action="store_true",
                        help="exit after the first session closes")
    args = parser.parse_args(argv)

    from bigdl_tpu.serve.cluster import init_worker_runtime
    init_worker_runtime()

    try:
        agent = ReplicaAgent(host=args.host, port=args.port,
                             token=args.token, once=args.once,
                             forward_events=True).start()
    except ValueError as e:
        print(f"replica agent: {e}", file=sys.stderr, flush=True)
        return 2
    # the machine-readable banner spawn_agent() waits for
    print(f"AGENT_PORT={agent.port}", flush=True)
    print(f"replica agent listening on {args.host}:{agent.port} "
          f"(pid {os.getpid()})", file=sys.stderr, flush=True)
    try:
        agent.done.wait()
    except KeyboardInterrupt:
        pass
    finally:
        agent.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
