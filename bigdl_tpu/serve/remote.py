"""Cross-host replicas over TCP: the stdio replica protocol on a
socket, with blip-tolerant reconnect (docs/serving.md "Cross-host
fleet").

A :class:`RemoteReplica` speaks to a replica agent
(``tools/replica_agent.py``) listening on ``host:port`` and wears the
EXACT :class:`~bigdl_tpu.serve.cluster.ProcessReplica` surface — the
router, the pool's rollout/membership machinery, the fleet's
page-shipping submit path and the autoscaler all take it unchanged.
The wire is the same hardened frame codec as the stdio pipes
(``serve/frames.py``), carrying the same op set; the agent hosts the
same :class:`~bigdl_tpu.serve.cluster.WorkerOps` the subprocess worker
runs, so the op vocabulary cannot diverge between transports.

What a socket adds over a pipe is a FAILURE MODE the pipe never had: a
pipe dies exactly when the replica dies, but a TCP connection can drop
while the replica is perfectly healthy.  The robustness core here is
telling those apart:

- **network blip** (connection lost < liveness budget): the client
  reconnects with backoff and re-attaches to the SAME agent session —
  session id + contiguous per-frame sequence numbers let the agent
  replay un-acked frames (replies, token chunks) and the client replay
  un-answered requests, each side deduplicating (``seq`` on the way
  down, request ids on the way up).  Zero requeues, zero duplicate
  token chunks (the StreamFuture's absolute-index dedup is the second
  belt), the session epoch unchanged.  During the blip ``alive()``
  stays True — the router keeps the replica in its dispatch set and
  its in-flight futures pending.
- **replica death / sustained partition** (budget exceeded, or the
  agent lost the session): the client converts to the existing
  :class:`~bigdl_tpu.serve.router.DeadReplicaError` path — every
  outstanding future fails typed, the router requeues each EXACTLY
  once on survivors, and the leased host returns to the inventory.

A silent black hole (packets dropped, socket not closed) is caught by
the keepalive: every ``liveness/4`` the client pings (measuring
``remote_rtt_seconds`` and piggybacking its ack watermark); a peer
quiet for a full budget gets its socket force-dropped so the reader
enters the reconnect path deterministically.

``HostInventory`` turns ``BIGDL_SERVE_HOSTS="h1:7070,h2:7070"`` into
the lease pool ReplicaPool/DecodeFleet spawn from — scale-up leases an
address, replica death or scale-down releases it, and an exhausted
inventory raises :class:`~bigdl_tpu.serve.cluster.ReplicaSpawnError`
(the type the autoscaler's circuit breaker already keys on).

Flags: ``BIGDL_SERVE_HOSTS`` (agent inventory), ``BIGDL_SERVE_TOKEN``
(shared-secret handshake), ``BIGDL_SERVE_LIVENESS_S`` (blip budget,
default 2.0).
"""
from __future__ import annotations

import logging
import os
import pickle
import socket
import subprocess
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from bigdl_tpu.serve.cluster import (_EXC_TYPES, _STDERR_LINES,
                                     ReplicaSpawnError, child_process_env)
from bigdl_tpu.serve.frames import FrameProtocolError
from bigdl_tpu.serve.frames import read_frame as _read_frame
from bigdl_tpu.serve.frames import read_welcome, write_hello
from bigdl_tpu.serve.frames import write_frame as _write_frame
from bigdl_tpu.serve.router import DeadReplicaError
from bigdl_tpu.serve.streaming import StreamFuture, TokenDelivery
from bigdl_tpu.utils.engine import CHECKOUT

logger = logging.getLogger("bigdl_tpu.serve")

ENV_HOSTS = "BIGDL_SERVE_HOSTS"
ENV_TOKEN = "BIGDL_SERVE_TOKEN"
ENV_LIVENESS = "BIGDL_SERVE_LIVENESS_S"

#: default blip budget (seconds): a connection loss shorter than this
#: is a network blip (reconnect + re-attach, zero requeues); longer is
#: a death (DeadReplicaError → requeue-exactly-once)
DEFAULT_LIVENESS_S = 2.0


def parse_hosts(spec) -> list:
    """``"h1:7070,h2:7071"`` (or an iterable of ``"h:p"`` /
    ``(h, p)``) → list of ``(host, port)`` tuples."""
    if spec is None:
        return []
    if isinstance(spec, str):
        items = [s for s in (p.strip() for p in spec.split(",")) if s]
    else:
        items = list(spec)
    out = []
    for item in items:
        if isinstance(item, (tuple, list)):
            host, port = item
        else:
            host, _, port = str(item).rpartition(":")
            if not host:
                raise ValueError(
                    f"bad host entry {item!r} (want host:port)")
        out.append((str(host), int(port)))
    return out


def hosts_default() -> list:
    return parse_hosts(os.environ.get(ENV_HOSTS, ""))


def token_default() -> str:
    return os.environ.get(ENV_TOKEN, "")


def liveness_default() -> float:
    try:
        return float(os.environ.get(ENV_LIVENESS, "")
                     or DEFAULT_LIVENESS_S)
    except ValueError:
        return DEFAULT_LIVENESS_S


class HostInventory:
    """The lease pool of replica-agent addresses a cross-host pool
    scales over.  ``lease()`` hands out a free address (exhaustion
    raises :class:`ReplicaSpawnError` — the autoscaler's circuit
    breaker trips instead of crash-looping) and ``release()`` returns
    one on replica death, scale-down, or spawn failure."""

    def __init__(self, hosts=None, token=None):
        hosts = parse_hosts(hosts) if hosts is not None else hosts_default()
        if not hosts:
            raise ValueError(
                f"cross-host pool needs agent addresses: pass hosts= "
                f"or set {ENV_HOSTS}=host:port[,host:port...]")
        self.token = token if token is not None else token_default()
        self._lock = threading.Lock()
        self._free = list(hosts)
        self._leased = []

    def lease(self):
        with self._lock:
            if not self._free:
                raise ReplicaSpawnError(
                    f"host inventory exhausted ({len(self._leased)} "
                    f"leased, 0 free): scale-up is capped by the "
                    f"{ENV_HOSTS} inventory")
            addr = self._free.pop(0)
            self._leased.append(addr)
            return addr

    def release(self, addr):
        with self._lock:
            if addr in self._leased:
                self._leased.remove(addr)
                self._free.append(addr)

    def stats(self) -> dict:
        with self._lock:
            return {"free": len(self._free), "leased": len(self._leased)}


class _Conn:
    """One TCP connection's socket + buffered file pair."""

    __slots__ = ("sock", "rfile", "wfile")

    def __init__(self, sock):
        self.sock = sock
        self.rfile = sock.makefile("rb")
        self.wfile = sock.makefile("wb")

    def force_drop(self):
        """Abort the connection from another thread: the reader's
        blocking read fails immediately (the keepalive's black-hole
        escape hatch)."""
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

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


class _HandshakeRefused(RuntimeError):
    """The agent answered the hello with a typed refusal (bad token,
    unknown session) — permanent, retrying cannot help."""


class RemoteReplica:
    """A serve replica hosted by a TCP agent, wearing ProcessReplica's
    surface (submit/inflight/alive/stats/telemetry + the rollout verbs)
    with blip-tolerant reconnect.  See the module docstring for the
    blip-vs-death semantics; ``agent=`` optionally attaches a loopback
    :class:`AgentHandle` so death errors carry the agent's stderr
    tail."""

    #: flight-recorder transport attribution (obs/recorder.py)
    transport = "tcp"

    #: role the init frame declares; subclasses repoint it
    def _init_frame(self, model, worker_kwargs) -> dict:
        return {"op": "init", "model": model, "engine": worker_kwargs}

    def __init__(self, addr, model, name: str = "remote", token=None,
                 liveness_s: float | None = None, on_release=None,
                 spawn_timeout: float = 120.0, agent=None,
                 **engine_kwargs):
        self.addr = (str(addr[0]), int(addr[1]))
        self.name = name
        self.token = token if token is not None else token_default()
        self.liveness_s = (liveness_default() if liveness_s is None
                           else float(liveness_s))
        self._on_release = on_release
        self._agent = agent
        self._lock = threading.Lock()
        self._wlock = threading.Lock()
        self._futures: dict = {}    # rid -> (future, trace-or-None)
        self._pending: dict = {}    # rid -> frame (replayed on re-attach)
        self._ids = iter(range(1, 1 << 62))
        self._dead = False
        self._closing = False
        self._conn: _Conn | None = None
        self._session = None
        self._epoch = None
        self._acked = 0             # highest peer seq seen (dedup + ack)
        self._last_rx = time.monotonic()
        self._delivery = None
        self._ready = threading.Event()

        from bigdl_tpu.obs import metrics as obs_metrics
        reg = obs_metrics.get()
        lab = {"replica": self.name}
        self._m_reconnects = reg.counter(
            "remote_reconnects_total",
            "successful same-session re-attaches after a network blip",
            **lab)
        self._m_sessions = reg.gauge(
            "remote_sessions", "live agent sessions held by this client",
            **lab)
        self._m_rtt = reg.histogram(
            "remote_rtt_seconds",
            "keepalive ping round-trip to the replica agent", **lab)

        try:
            conn, welcome = self._dial(resume=False)
        except (_HandshakeRefused, FrameProtocolError, OSError,
                ValueError, EOFError, pickle.PickleError) as e:
            raise ReplicaSpawnError(
                f"replica {name}: agent {self.addr[0]}:{self.addr[1]} "
                f"refused the handshake: {type(e).__name__}: {e}"
                f"{self._agent_tail_suffix()}",
                stderr_tail=self._agent_stderr()) from e
        self._conn = conn
        self._session = welcome.get("session")
        self._epoch = welcome.get("epoch")
        self._m_sessions.set(1)
        from bigdl_tpu.obs import events as obs_events
        obs_events.emit("remote", kind="connect", replica=self.name,
                        address=f"{self.addr[0]}:{self.addr[1]}")

        engine_kwargs = dict(engine_kwargs)
        engine_kwargs.setdefault("name", name)
        # the init frame rides the session like any request (it has a
        # rid and sits in _pending), so a blip during the agent-side
        # model build replays it and the rid dedup makes that harmless
        rid = next(self._ids)
        self._init_rid = rid
        frame = dict(self._init_frame(model, engine_kwargs), id=rid)
        with self._lock:
            self._pending[rid] = frame
        try:
            _write_frame(conn.wfile, frame, self._wlock)
        except (OSError, ValueError) as e:
            self._teardown_conn()
            raise ReplicaSpawnError(
                f"replica {name}: init frame to "
                f"{self.addr[0]}:{self.addr[1]} failed: {e}"
                f"{self._agent_tail_suffix()}",
                stderr_tail=self._agent_stderr()) from e
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"bigdl-serve-{name}-reader")
        self._reader.start()
        self._keepalive = threading.Thread(
            target=self._keepalive_loop, daemon=True,
            name=f"bigdl-serve-{name}-keepalive")
        self._keepalive.start()
        if not self._ready.wait(spawn_timeout):
            self._teardown_conn()
            self._on_death()
            raise ReplicaSpawnError(
                f"replica {name} did not come up in {spawn_timeout}s"
                f"{self._agent_tail_suffix()}",
                stderr_tail=self._agent_stderr())
        if self._dead:
            raise ReplicaSpawnError(
                f"replica {name} died during startup"
                f"{self._agent_tail_suffix()}",
                stderr_tail=self._agent_stderr())

    # -- session surface ----------------------------------------------------
    @property
    def session_epoch(self):
        """The agent-side epoch of the session this client holds — the
        blip-vs-death witness: unchanged across a survived blip, new
        only with a new session (i.e. a new replica)."""
        return self._epoch

    # -- wire ---------------------------------------------------------------
    def _dial(self, resume: bool):
        """Connect + authenticate.  Returns ``(conn, welcome)``; raises
        OSError-family on transient failure (the partition may still
        heal) or :class:`_HandshakeRefused` on a typed refusal.  The
        hello/welcome exchange is the fixed pickle-free handshake
        layout (``serve/frames.py``) — neither peer unpickles anything
        before the token check passes."""
        timeout = max(2.0, self.liveness_s)
        sock = socket.create_connection(self.addr, timeout=timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _Conn(sock)
        try:
            write_hello(conn.wfile, token=self.token,
                        session=self._session if resume else None,
                        acked=self._acked, name=self.name)
            welcome = read_welcome(conn.rfile)
            if welcome is None:
                raise OSError("agent closed the connection mid-handshake")
            if welcome.get("op") == "error":
                raise _HandshakeRefused(
                    welcome.get("error", "agent refused the handshake"))
            if resume and not welcome.get("resumed"):
                raise _HandshakeRefused(
                    "agent did not resume the session")
        except BaseException:
            conn.close()
            raise
        sock.settimeout(None)
        self._last_rx = time.monotonic()
        return conn, welcome

    def _teardown_conn(self):
        conn, self._conn = self._conn, None
        if conn is not None:
            conn.close()

    def _read_loop(self):
        while True:
            conn = self._conn
            if conn is None:
                return
            try:
                msg = _read_frame(conn.rfile)
            except FrameProtocolError as e:
                # corrupt/desynced bytes: drop the connection — the
                # re-attach replay restores anything the cut lost
                logger.warning("replica %s: %s; dropping connection",
                               self.name, e)
                msg = None
            except (OSError, ValueError, EOFError, pickle.PickleError):
                msg = None
            if msg is None:
                if self._closing or self._dead:
                    self._on_death()
                    return
                if self._reconnect():
                    continue
                self._on_death()
                return
            self._last_rx = time.monotonic()
            seq = msg.get("seq")
            if seq is not None:
                if seq <= self._acked:
                    # a replayed frame this client already consumed
                    # before the blip — the downstream dedup belt
                    continue
                self._acked = seq
            try:
                self._handle(msg)
            except Exception:
                # a reply-handling bug (double-resolve, delivery
                # failure, ...) must not silently kill the only thread
                # that resolves futures — alive() would stay True and
                # the router would keep dispatching to a wedged
                # replica.  Convert it to the death path: orphans fail
                # typed and the router requeues.
                logger.exception(
                    "replica %s: reply handling failed; converting to "
                    "replica death", self.name)
                self._on_death()
                return

    def _handle(self, msg):
        op = msg.get("op")
        if op == "ready":
            with self._lock:
                self._pending.pop(self._init_rid, None)
                self._futures.pop(self._init_rid, None)
            self._ready.set()
            return
        if op == "event":
            self._forward_event(msg.get("event"))
            return
        if op == "tokens":
            with self._lock:
                entry = self._futures.get(msg.get("id"))
            if entry is not None:
                self._ensure_delivery().enqueue(
                    entry[0], msg.get("tokens") or [],
                    msg.get("start"), None)
            return
        with self._lock:
            entry = self._futures.pop(msg.get("id"), None)
            self._pending.pop(msg.get("id"), None)
        if entry is None:
            return
        fut, tr = entry
        if msg.get("ok"):
            if tr is not None:
                tr.extend(msg.get("hops") or ())
                if msg.get("rec"):
                    # the agent-side flight-recorder notes merge into
                    # this client's record (same frame as the hops)
                    from bigdl_tpu.obs import recorder as obs_recorder
                    obs_recorder.note(tr.trace_id, **msg["rec"])
            if fut.streaming and self._delivery is not None:
                self._delivery.resolve(fut, msg.get("out"))
            else:
                fut.set_result(msg.get("out"))
        else:
            cls = _EXC_TYPES.get(msg.get("etype"), RuntimeError)
            fut.set_exception(cls(msg.get("error", "replica error")))

    def _reconnect(self) -> bool:
        """The blip path: reconnect + re-attach to the same session
        within the liveness budget.  True = re-attached (reader
        continues, zero requeues); False = this replica is dead."""
        from bigdl_tpu.obs import events as obs_events
        t0 = time.monotonic()
        deadline = t0 + self.liveness_s
        self._teardown_conn()
        obs_events.emit("remote", kind="blip", replica=self.name)
        # requests in flight across the blip: note the partition
        # involvement so the recorder's terminal classification keeps
        # their full timeline even when they resolve healthy
        from bigdl_tpu.obs import recorder as obs_recorder
        with self._lock:
            blipped = [t for _, t in self._futures.values()
                       if t is not None]
        for t in blipped:
            obs_recorder.note(t.trace_id, blip_replica=self.name)
        logger.warning("replica %s: connection to %s:%d lost; "
                       "reconnecting (budget %.2fs)", self.name,
                       self.addr[0], self.addr[1], self.liveness_s)
        backoff = 0.02
        while time.monotonic() < deadline and not self._closing:
            try:
                conn, welcome = self._dial(resume=True)
            except _HandshakeRefused as e:
                # the agent lost the session (restart, TTL reap, a new
                # client superseded us): no amount of retrying re-attaches
                logger.warning("replica %s: re-attach refused: %s",
                               self.name, e)
                return False
            except (FrameProtocolError, OSError, ValueError, EOFError,
                    pickle.PickleError):
                time.sleep(min(backoff,
                               max(0.0, deadline - time.monotonic())))
                backoff = min(backoff * 2, 0.25)
                continue
            self._conn = conn
            # replay every un-answered request in rid order; the agent
            # dedups rids it already executed, and its outbox replay
            # (driven by our acked watermark in the hello) restores any
            # replies/chunks the cut swallowed
            with self._lock:
                replay = sorted(self._pending.items())
            try:
                for _, frame in replay:
                    _write_frame(conn.wfile, frame, self._wlock)
            except (FrameProtocolError, OSError, ValueError):
                # the link died again mid-replay: loop — budget allowing
                self._teardown_conn()
                continue
            blip_s = time.monotonic() - t0
            self._m_reconnects.inc()
            obs_events.emit("remote", kind="reattach", replica=self.name,
                            blip_s=round(blip_s, 4))
            logger.warning("replica %s: re-attached to session %s after "
                           "%.3fs blip (%d requests replayed)",
                           self.name, self._session, blip_s, len(replay))
            return True
        return False

    def _keepalive_loop(self):
        """Ping cadence ``liveness/4``: measures RTT, carries the ack
        watermark that lets the agent prune its outbox, and force-drops
        a silently black-holed socket after a full quiet budget so the
        reader reaches the reconnect path."""
        period = max(0.05, self.liveness_s / 4.0)
        while not (self._closing or self._dead):
            time.sleep(period)
            conn = self._conn
            if conn is None or self._closing or self._dead:
                continue
            if not self._ready.is_set():
                # the agent is still building the replica (the init
                # compile can legitimately exceed the blip budget);
                # spawn_timeout owns this window
                continue
            if time.monotonic() - self._last_rx > self.liveness_s:
                logger.warning(
                    "replica %s: no frames for %.2fs (silent black "
                    "hole); force-dropping the socket", self.name,
                    self.liveness_s)
                conn.force_drop()
                continue
            t0 = time.monotonic()
            fut = self._send("ping", _replay=False, acked=self._acked)
            try:
                fut.result(timeout=self.liveness_s)
                self._m_rtt.observe(time.monotonic() - t0)
            except Exception:
                # lost ping: the reader/liveness machinery owns the
                # consequence; just drop the orphaned future
                with self._lock:
                    self._futures.pop(getattr(fut, "_rid", None), None)

    def _forward_event(self, event):
        if not isinstance(event, dict):
            return
        try:
            from bigdl_tpu.obs import events as obs_events
            log = obs_events.get()
            if log is not None:
                log.append_foreign(event, replica=self.name)
        except Exception:  # pragma: no cover - telemetry must not kill IO
            logger.warning("replica %s: event forward failed", self.name)

    def _agent_stderr(self):
        return (self._agent.stderr_tail()
                if self._agent is not None else None)

    def _agent_tail_suffix(self, n: int = 8) -> str:
        tail = self._agent_stderr()
        if not tail:
            return ""
        return "; agent stderr tail:\n  " + "\n  ".join(tail[-n:])

    def _dead_error(self) -> DeadReplicaError:
        return DeadReplicaError(
            f"replica {self.name} (agent {self.addr[0]}:{self.addr[1]}) "
            f"died{self._agent_tail_suffix()}")

    def _on_death(self):
        with self._lock:
            if self._dead:
                return
            self._dead = True
            orphans = [f for f, _ in self._futures.values()]
            self._futures.clear()
            self._pending.clear()
        self._ready.set()
        self._teardown_conn()
        try:
            self._m_sessions.set(0)
        except Exception:   # pragma: no cover - registry mid-teardown
            pass
        err = self._dead_error()
        for fut in orphans:
            if not fut.done():
                fut.set_exception(err)
        if not self._closing:
            from bigdl_tpu.obs import events as obs_events
            obs_events.emit("remote", kind="death", replica=self.name,
                            orphaned_requests=len(orphans))
        if self._on_release is not None:
            try:
                self._on_release(self.addr)
            except Exception:   # pragma: no cover - inventory teardown
                pass
            self._on_release = None

    def _ensure_delivery(self) -> TokenDelivery:
        if self._delivery is None:
            self._delivery = TokenDelivery(name=self.name)
        return self._delivery

    def _rpc(self, op: str, timeout: float | None = None, **fields):
        fut = self._send(op, **fields)
        return fut.result(timeout=timeout)

    def _send(self, op: str, _trace=None, _replay=True, **fields) -> Future:
        rid = next(self._ids)
        fut = StreamFuture()
        fut._rid = rid
        frame = dict(fields, op=op, id=rid)
        with self._lock:
            if self._dead:
                fut.set_exception(self._dead_error())
                return fut
            self._futures[rid] = (fut, _trace)
            if _replay:
                self._pending[rid] = frame
        conn = self._conn
        try:
            if conn is not None:
                _write_frame(conn.wfile, frame, self._wlock)
        except FrameProtocolError as e:
            # over-bound payload: nothing was written, only this rpc
            # fails — the connection (and replica) live on
            with self._lock:
                self._futures.pop(rid, None)
                self._pending.pop(rid, None)
            fut.set_exception(e)
        except (OSError, ValueError):
            # mid-blip write: tolerated — the frame sits in _pending
            # and replays on re-attach (or orphans on death)
            pass
        return fut

    # -- replica surface (ProcessReplica parity) ----------------------------
    def submit(self, x, trace=None) -> Future:
        return self._send(
            "submit", _trace=trace, x=np.asarray(x),
            trace=None if trace is None else trace.to_wire())

    def inflight(self) -> int:
        with self._lock:
            return len(self._futures)

    def alive(self) -> bool:
        # True through a blip: the router must NOT requeue this
        # replica's work while a reconnect is still inside the budget
        return not self._dead

    def stats(self) -> dict:
        return self._rpc("stats", timeout=30.0)

    def telemetry(self) -> dict:
        return self._rpc("telemetry", timeout=30.0)

    def registry_snapshot(self) -> dict | None:
        return self.telemetry().get("registry")

    def weights_version(self) -> int:
        return self._rpc("version", timeout=30.0)

    def stage_weights(self, params, state, version=None):
        self._rpc("stage", timeout=120.0, params=params, state=state,
                  version=version)

    def commit_weights(self) -> int:
        return self._rpc("commit", timeout=30.0)

    def rollback_weights(self):
        self._rpc("rollback", timeout=30.0)

    def revert_weights(self) -> int:
        return self._rpc("revert", timeout=30.0)

    def close(self, drain: bool = True):
        self._closing = True
        if not self._dead and self._conn is not None:
            try:
                self._rpc("close", timeout=60.0, drain=drain)
            except Exception:
                pass
        self._on_death()
        if threading.current_thread() is not self._reader:
            self._reader.join(timeout=10.0)
        if self._delivery is not None:
            self._delivery.close()
            self._delivery = None
        try:
            from bigdl_tpu.obs import metrics as obs_metrics
            obs_metrics.get().drop_series(replica=self.name)
        except Exception:   # pragma: no cover - registry mid-teardown
            pass


class RemoteDecodeReplica(RemoteReplica):
    """A fleet decode replica behind a TCP agent: ProcessDecodeReplica's
    submit surface (shipped pages, streamed token chunks) on the
    blip-tolerant transport.  Shipped page bytes land on
    ``fleet_ship_bytes_total{transport="tcp"}``."""

    def _init_frame(self, model, worker_kwargs) -> dict:
        return {"op": "init", "role": "decode", "model": model,
                "decoder": worker_kwargs}

    def submit(self, x, trace=None) -> Future:
        from bigdl_tpu.serve.fleet import _note_ship_bytes
        _note_ship_bytes(self.name, "tcp", x.get("pages"))
        return self._send(
            "submit", _trace=trace,
            seed=[int(t) for t in x["seed"]],
            n_words=int(x["n_words"]), pages=x.get("pages"),
            stream=bool(x.get("stream")),
            sampling=x.get("sampling"),
            trace=None if trace is None else trace.to_wire())


class RemotePrefillReplica(RemoteReplica):
    """A fleet prefill replica behind a TCP agent — ``prefill_async``
    resolves to the shippable page payloads, death falls back to
    colocated prefill via the FleetRouter's existing path."""

    def _init_frame(self, model, worker_kwargs) -> dict:
        return {"op": "init", "role": "prefill", "model": model,
                "prefill": worker_kwargs}

    def prefill_async(self, seed) -> Future:
        return self._send("prefill", seed=[int(t) for t in seed])

    def prefill(self, seed, timeout: float = 120.0) -> list:
        return self.prefill_async(seed).result(timeout=timeout)


# ---------------------------------------------------------------------------
# loopback agent spawning (tests, single-host demos, bench)
# ---------------------------------------------------------------------------

class AgentHandle:
    """A locally spawned replica-agent subprocess: its address, its
    bounded stderr ring (the tail rides DeadReplicaError /
    ReplicaSpawnError messages), and kill/close for drills."""

    def __init__(self, proc, host: str, port: int):
        self.proc = proc
        self.host, self.port = host, port
        self._ring = deque(maxlen=_STDERR_LINES)
        self._stderr_reader = threading.Thread(
            target=self._stderr_loop, daemon=True,
            name=f"bigdl-agent-{port}-stderr")
        self._stderr_reader.start()

    @property
    def addr(self):
        return (self.host, self.port)

    def _stderr_loop(self):
        try:
            for raw in self.proc.stderr:
                self._ring.append(
                    raw.decode("utf-8", errors="replace").rstrip("\n"))
        except (OSError, ValueError):  # pragma: no cover - teardown
            pass

    def stderr_tail(self, n: int | None = None) -> list:
        tail = list(self._ring)
        return tail if n is None else tail[-n:]

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self):
        """Induced agent death (the real-death drill)."""
        try:
            self.proc.kill()
        except OSError:   # pragma: no cover - already gone
            pass

    def close(self):
        self.kill()
        try:
            self.proc.wait(timeout=10.0)
        except Exception:   # pragma: no cover - still exiting
            pass
        self._stderr_reader.join(timeout=2.0)


def spawn_agent(host: str = "127.0.0.1", port: int = 0, token=None,
                env=None, spawn_timeout: float = 60.0) -> AgentHandle:
    """Spawn ``python -m tools.replica_agent`` on a loopback port and
    wait for its ``AGENT_PORT=<n>`` banner.  Returns the
    :class:`AgentHandle` whose ``.addr`` a RemoteReplica dials."""
    overrides = {} if token is None else {ENV_TOKEN: str(token)}
    overrides.update(env or {})
    child_env = child_process_env(overrides)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tools.replica_agent",
         "--host", host, "--port", str(port)],
        stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=child_env, cwd=CHECKOUT)
    handle = AgentHandle(proc, host, port)
    deadline = time.monotonic() + spawn_timeout
    killer = threading.Timer(spawn_timeout, proc.kill)
    killer.daemon = True
    killer.start()
    try:
        while True:
            line = proc.stdout.readline()
            if not line:
                raise ReplicaSpawnError(
                    f"replica agent on {host}:{port} exited before "
                    f"announcing its port (exit {proc.poll()}); stderr "
                    f"tail:\n  " + "\n  ".join(handle.stderr_tail(8)),
                    stderr_tail=handle.stderr_tail())
            text = line.decode("utf-8", errors="replace").strip()
            if text.startswith("AGENT_PORT="):
                handle.port = int(text.split("=", 1)[1])
                return handle
            if time.monotonic() > deadline:
                raise ReplicaSpawnError(
                    f"replica agent on {host}:{port} did not announce "
                    f"its port in {spawn_timeout}s",
                    stderr_tail=handle.stderr_tail())
    except BaseException:
        handle.close()
        raise
    finally:
        killer.cancel()
