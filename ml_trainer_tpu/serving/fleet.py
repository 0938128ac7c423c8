"""True multi-process serving fleet (docs/serving.md "Multi-process
fleet").

Every replica is its own OS PROCESS — a real ``Server`` behind its
stdlib HTTP front end — and the router in the driver process talks to
it ONLY over sockets.  Two pieces:

* :class:`RemoteServer` — a duck-typed stand-in for the in-process
  ``Server`` that the existing :class:`~ml_trainer_tpu.serving.Router`
  (and autoscaler, degradation ladder, chaos harness) drives
  unmodified.  Token streams ride ``POST /v1/stream`` NDJSON; KV
  migration ships the serialized :class:`KVSlotExport` bytes over
  ``POST /v1/adopt`` with the CRC verified at the RECEIVING process,
  whose structured verdict (``corrupt`` / ``no_memory`` / ``draining``
  / ``unhealthy``) maps back into the router's fallback-candidate
  machinery as the same exceptions the in-process path raises.

* :class:`Fleet` — the launcher: spawns each replica as
  ``python -m ml_trainer_tpu.serving.fleet --worker ...`` with its own
  port, role, pool geometry and a SHARED on-disk compile cache, waits
  for readiness, and hands the router a ``{name: RemoteServer}`` map.
  ``Fleet.factory`` is an autoscaler ``server_factory`` that spawns a
  REAL process per scale-up; ``RemoteServer.kill_process`` is a real
  ``SIGKILL`` (the chaos ``replica_kill`` path), and ``close`` is a
  graceful shutdown only after evacuation.

Determinism across processes: every worker builds the model with the
same ``jax.random.PRNGKey(seed)`` init, so weights are identical in
every process without shipping checkpoints, and migration is
byte-exact by the same CRC + step-counter machinery the in-process
router pins.
"""

from __future__ import annotations

import base64
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import types
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence

from ml_trainer_tpu.trainer import COMPILE_CACHE_DIR
from ml_trainer_tpu.utils.logging import get_logger
from ml_trainer_tpu.serving.overload import OverloadShed
from ml_trainer_tpu.serving.scheduler import (
    AdmissionError,
    EngineUnhealthy,
    Request,
)
from ml_trainer_tpu.serving.transfer import (
    MigrationCorrupt,
    WeightsMismatch,
    request_wire_meta,
)

# The router's migration sentinel (api.py carries the same literal so
# api never has to import router).
_MIGRATE = "__kv_migrate__"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _RemoteSlo:
    """``server.slo`` facade over ``GET /slo`` — the router's publish
    loop reads ``snapshot()["attainment"]`` per replica; a dead process
    degrades to perfect attainment instead of wedging the poller."""

    def __init__(self, remote: "RemoteServer"):
        self._remote = remote

    def snapshot(self) -> dict:
        try:
            return self._remote._get("/slo")
        except Exception:
            return {"attainment": {"ttft": 1.0, "tpot": 1.0}}

    def forget(self, req) -> None:  # shadow bookkeeping is local-only
        pass


class RemoteServer:
    """HTTP proxy for one replica PROCESS, duck-typed to the surface
    the router/autoscaler/ladder expect from an in-process ``Server``.

    The constructor fetches ``GET /v1/spec`` and mirrors the engine
    geometry into ``self.engine`` / ``self.scheduler`` namespaces so
    the router's geometry validation, placement math and inflight
    budget work unchanged.  ``submit_request``/``adopt_payload`` open
    long-lived NDJSON streams and pump tokens into the SHADOW request
    from a daemon thread; a severed socket (SIGKILL'd replica) finishes
    the shadow with a retryable ``unhealthy`` error, so the router
    redistributes from the committed prefix exactly like the
    in-process kill path."""

    def __init__(self, url: str, proc: Optional[subprocess.Popen] = None,
                 name: str = "", stream_timeout: float = 600.0,
                 log_path: Optional[str] = None):
        self.url = url.rstrip("/")
        self.proc = proc
        self.name = name or self.url
        self.transport = "http"
        self.log_path = log_path
        self._stream_timeout = float(stream_timeout)
        self._log = get_logger("ml_trainer_tpu.serving.fleet")
        spec = self._get("/v1/spec", timeout=10.0)
        self.pid = spec.get("pid")
        self.engine = types.SimpleNamespace(
            max_len=int(spec["max_len"]),
            vocab_size=int(spec["vocab_size"]),
            spec_k=int(spec["spec_k"]),
            kv_page_size=int(spec["kv_page_size"]),
            paged=bool(spec["paged"]),
            max_batch=int(spec["max_batch"]),
            prefill_chunk=int(spec.get("prefill_chunk", 0)),
            weights_fp=spec.get("weights_fp"),
        )
        self.scheduler = types.SimpleNamespace(
            max_queue=int(spec["max_queue"])
        )
        self._role = spec.get("role", "both")
        self._replica_index = 0
        self.slo = _RemoteSlo(self)

    # -- plumbing ---------------------------------------------------------

    def _get(self, path: str, timeout: float = 5.0) -> dict:
        with urllib.request.urlopen(
            f"{self.url}{path}", timeout=timeout
        ) as resp:
            return json.loads(resp.read())

    def _get_text(self, path: str, timeout: float = 5.0) -> str:
        with urllib.request.urlopen(
            f"{self.url}{path}", timeout=timeout
        ) as resp:
            return resp.read().decode("utf-8", errors="replace")

    def metrics_text(self, timeout: float = 5.0) -> str:
        """Raw Prometheus text from the worker's ``/metrics`` — what
        the router's federation scrape re-exports with replica labels."""
        return self._get_text("/metrics", timeout=timeout)

    def _post(self, path: str, body: dict, timeout: float = 10.0) -> dict:
        data = json.dumps(body).encode()
        req = urllib.request.Request(
            f"{self.url}{path}", data=data,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    def _open_stream(self, path: str, data: bytes, headers: dict,
                     timeout: float):
        """POST and return the live close-delimited NDJSON response."""
        req = urllib.request.Request(
            f"{self.url}{path}", data=data, headers=headers
        )
        return urllib.request.urlopen(req, timeout=timeout)

    @staticmethod
    def _read_line(resp) -> Optional[dict]:
        line = resp.readline()
        if not line:
            return None
        return json.loads(line)

    # -- health / role surface --------------------------------------------

    @property
    def role(self) -> str:
        return self._role

    @role.setter
    def role(self, value: str) -> None:
        self._role = value
        self._post("/admin/role", {"role": value})

    @property
    def replica_index(self) -> int:
        return self._replica_index

    @replica_index.setter
    def replica_index(self, value: int) -> None:
        self._replica_index = int(value)
        try:  # best effort — reindex runs right after a SIGKILL too
            self._post("/admin/replica_index", {"replica_index": value},
                       timeout=2.0)
        except Exception:
            pass

    def stderr_tail(self, max_bytes: int = 2048) -> Optional[str]:
        """Bounded tail of the worker's combined stdout+stderr log —
        the post-mortem a post-ready crash would otherwise lose (the
        readiness handshake only surfaces PRE-ready exits).  The
        autoscaler attaches it to the replace-dead flight event."""
        if not self.log_path:
            return None
        try:
            with open(self.log_path, "rb") as fp:
                fp.seek(0, os.SEEK_END)
                size = fp.tell()
                fp.seek(max(size - int(max_bytes), 0))
                return fp.read().decode("utf-8", errors="replace")
        except OSError:
            return None

    def health(self) -> dict:
        try:
            return self._get("/healthz", timeout=2.0)
        except urllib.error.HTTPError as e:  # 503 still carries it
            try:
                return json.loads(e.read())
            except Exception:
                return {"ok": False, "healthy": False, "closed": True,
                        "reason": f"healthz HTTP {e.code}"}
        except Exception as e:
            return {"ok": False, "healthy": False, "closed": True,
                    "reason": f"replica process unreachable: {e}"}

    # -- request path -----------------------------------------------------

    def _raise_refusal(self, first: Optional[dict]) -> None:
        """Map a first-line refusal onto the in-process exceptions."""
        if first is None:
            raise EngineUnhealthy(
                "serving engine unhealthy: replica closed the "
                "connection before the admission verdict"
            )
        status = first.get("status")
        err = first.get("error", status)
        if status == "shed":
            raise OverloadShed(err, retry_after=first.get("retry_after"))
        if status == "draining":
            raise AdmissionError(err)
        if status == "unhealthy":
            raise EngineUnhealthy(err)
        if status == "closed":
            raise RuntimeError(err)
        if status == "corrupt":
            raise MigrationCorrupt(err)
        if status == "weights_mismatch":
            raise WeightsMismatch(err)
        if status == "no_memory":
            raise AdmissionError(f"adoption refused (no_memory): {err}")
        raise RuntimeError(f"unexpected fleet reply: {first}")

    def _pump_stream(self, shadow: Request, resp) -> None:
        """Daemon-thread body: NDJSON lines -> the shadow request.  A
        ``migrated`` terminal leaves the shadow UNFINISHED — the export
        already rode an ``m`` line into its stream and the router's
        pump adopts it elsewhere.  Any transport failure is a
        retryable ``unhealthy`` finish (redistribute, don't surface)."""
        from ml_trainer_tpu.serving import transfer

        try:
            with resp:
                while True:
                    obj = self._read_line(resp)
                    if obj is None:
                        shadow.finish(
                            "error",
                            "serving engine unhealthy: replica "
                            f"'{self.name}' connection lost mid-stream",
                        )
                        return
                    if "t" in obj:
                        shadow.push_token(int(obj["t"]))
                        continue
                    if "m" in obj:
                        payload = base64.b64decode(obj["m"])
                        try:
                            export = transfer.from_bytes(payload)
                        except MigrationCorrupt as e:
                            shadow.finish(
                                "error",
                                "serving engine unhealthy: migration "
                                f"payload corrupt in transit from "
                                f"'{self.name}': {e}",
                            )
                            return
                        shadow._stream.put((_MIGRATE, export))
                        continue
                    done = obj.get("done")
                    if done is not None:
                        state = done.get("state")
                        if state == "migrated":
                            return  # adoption continues the stream
                        if done.get("retry_after") is not None:
                            shadow.retry_after = done["retry_after"]
                        shadow.finish(state, done.get("error"))
                        return
        except Exception as e:  # severed socket, timeout, bad line
            shadow.finish(
                "error",
                "serving engine unhealthy: replica "
                f"'{self.name}' stream failed mid-flight: {e}",
            )

    def _start_pump(self, shadow: Request, resp) -> None:
        threading.Thread(
            target=self._pump_stream, args=(shadow, resp), daemon=True,
            name=f"fleet-pump-{self.name}-{shadow.id}",
        ).start()

    def submit_request(self, shadow: Request) -> None:
        """``POST /v1/stream``: ship the request identity, read the
        synchronous admission verdict, then pump the token stream into
        the shadow from a daemon thread."""
        body = request_wire_meta(shadow)
        body["migrate"] = shadow.migration_sink is not None
        headers = {"Content-Type": "application/json"}
        if getattr(shadow, "trace_ctx", None):
            # The trace context also rides the wire meta; the header is
            # the RPC-level contract (api.py TRACE_HEADER) so even a
            # meta-stripping proxy keeps the request traceable.
            headers["X-Trace-Context"] = json.dumps(shadow.trace_ctx)
        try:
            resp = self._open_stream(
                "/v1/stream", json.dumps(body).encode(),
                headers,
                self._stream_timeout,
            )
            first = self._read_line(resp)
        except (OSError, ValueError) as e:
            raise EngineUnhealthy(
                "serving engine unhealthy: replica "
                f"'{self.name}' unreachable: {e}"
            )
        if first is None or first.get("status") != "accepted":
            with resp:
                self._raise_refusal(first)
        self._start_pump(shadow, resp)

    def adopt_payload(self, shadow: Request, payload: bytes) -> None:
        """``POST /v1/adopt``: the serialized ``KVSlotExport`` rides as
        the raw body (request identity in the ``X-Request-Meta``
        header); the receiving PROCESS verifies the CRC and replies a
        structured verdict mapped back onto the in-process adopt
        exceptions, so the router's fallback-candidate loop works
        unchanged.  On ``adopted`` the same connection becomes the
        continuation token stream."""
        meta = json.dumps(request_wire_meta(shadow))
        headers = {"Content-Type": "application/octet-stream",
                   "X-Request-Meta": meta}
        if getattr(shadow, "trace_ctx", None):
            headers["X-Trace-Context"] = json.dumps(shadow.trace_ctx)
        try:
            resp = self._open_stream(
                "/v1/adopt", payload,
                headers,
                self._stream_timeout,
            )
            first = self._read_line(resp)
        except (OSError, ValueError) as e:
            raise EngineUnhealthy(
                "serving engine unhealthy: replica "
                f"'{self.name}' unreachable for adoption: {e}"
            )
        status = (first or {}).get("status")
        if status == "adopted":
            self._start_pump(shadow, resp)
            return
        if status in ("error", "expired", "cancelled"):
            # Structured terminals the in-process path also surfaces by
            # finishing the request after a SUCCESSFUL adoption enqueue.
            with resp:
                state = "expired" if status == "expired" else "error"
                shadow.finish(state, first.get("error", status))
            return
        with resp:
            self._raise_refusal(first)

    def cancel(self, req: Request) -> None:
        req.cancel_requested = True
        try:
            self._post("/v1/cancel", {"id": int(req.id)}, timeout=5.0)
        except Exception:
            pass  # best effort — the replica may already be failing it

    # -- control surface --------------------------------------------------

    def evacuate(self, sink, timeout: float = 30.0) -> bool:
        """The exports ride each request's own open stream as ``m``
        lines (the router's pump adopts them), so the router-provided
        in-process ``sink`` is unused here."""
        del sink
        resp = self._post(
            "/admin/evacuate", {"timeout": timeout}, timeout=timeout + 10.0
        )
        return bool(resp.get("ok"))

    def set_degradation(self, level: int, config) -> None:
        import dataclasses

        cfg = (
            dataclasses.asdict(config)
            if dataclasses.is_dataclass(config) else dict(config or {})
        )
        try:
            self._post("/admin/degradation",
                       {"level": int(level), "config": cfg}, timeout=5.0)
        except Exception:
            pass  # ladder sweeps every replica; a dead one is fine

    def shed_queued(self, below_priority: int, retry_after: float,
                    cause: str = "overload") -> int:
        try:
            resp = self._post(
                "/admin/shed_queued",
                {"below_priority": int(below_priority),
                 "retry_after": float(retry_after), "cause": cause},
                timeout=5.0,
            )
            return int(resp.get("shed", 0))
        except Exception:
            return 0

    def _mark_unhealthy(self, reason: str) -> None:
        try:  # the process may already be SIGKILL'd — that's the point
            self._post("/admin/fail", {"reason": reason}, timeout=2.0)
        except Exception:
            pass

    def kill_process(self) -> None:
        """Real ``SIGKILL`` — the chaos/router ``replica_kill`` action.
        No cleanup runs in the replica; recovery is redistribution."""
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait(timeout=10.0)
        elif self.pid:
            try:
                os.kill(int(self.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def close(self) -> None:
        """Graceful shutdown: ask the process to exit, then reap it."""
        try:
            self._post("/admin/shutdown", {}, timeout=5.0)
        except Exception:
            pass
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10.0)


class Fleet:
    """Spawn-and-wire launcher for a multi-process replica fleet.

        fleet = Fleet(roles=["prefill", "decode", "decode"],
                      kv_page_size=16, prefill_chunk=32)
        fleet.start()
        router = fleet.make_router()   # owns the RemoteServers
        ...
        router.close(); fleet.stop()

    Worker processes share one on-disk XLA compile cache — wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it, else the checkout's fixed
    ``trainer.COMPILE_CACHE_DIR`` — and never inherit an active chaos
    plan: faults are the DRIVER's job, a worker must only ever die by
    real signal.

    Every worker is PINNED TO THE CPU (``JAX_PLATFORMS=cpu``, one host
    device): a chip belongs to one process, and putting replicas on
    chips (one process driving several devices, or one chip per worker
    with the parent off JAX) is ROADMAP R5a's work, not done here.  A
    fleet therefore measures nothing about a TPU."""

    def __init__(self, roles: Sequence[str], *,
                 model_name: str = "gpt2_tiny", max_len: int = 256,
                 max_batch: int = 4, max_queue: int = 64,
                 kv_page_size: int = 16, kv_pages: int = 0,
                 seed: int = 0, prefill_chunk: int = 0,
                 prefix_cache: bool = True,
                 host: str = "127.0.0.1",
                 log_dir: Optional[str] = None,
                 spawn_timeout: float = 180.0,
                 stream_timeout: float = 600.0):
        self.roles = list(roles)
        self.model_name = model_name
        self.max_len = int(max_len)
        self.max_batch = int(max_batch)
        self.max_queue = int(max_queue)
        self.kv_page_size = int(kv_page_size)
        self.kv_pages = int(kv_pages)
        self.seed = int(seed)
        self.prefill_chunk = int(prefill_chunk)
        self.prefix_cache = bool(prefix_cache)
        self.host = host
        self.spawn_timeout = float(spawn_timeout)
        self.stream_timeout = float(stream_timeout)
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="fleet-logs-")
        self.replicas: Dict[str, RemoteServer] = {}
        self._role_seq: Dict[str, int] = {}
        self._log = get_logger("ml_trainer_tpu.serving.fleet")

    # -- lifecycle --------------------------------------------------------

    def _next_name(self, role: str) -> str:
        n = self._role_seq.get(role, 0)
        self._role_seq[role] = n + 1
        return f"{role}{n}"

    def _worker_env(self) -> dict:
        env = dict(os.environ)
        # Chaos plans fire in the DRIVER (router) process only; a
        # worker inheriting one would double-fire every fault.
        env.pop("ML_TRAINER_TPU_FAULTS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        # Placed from outside if the variable is set; JAX reads it itself.
        env.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)
        return env

    def spawn(self, name: str, role: str,
              ckpt: Optional[str] = None) -> RemoteServer:
        """Spawn one replica process and block until its HTTP front end
        answers ``/v1/spec`` (the compile-warm readiness gate).  With
        ``ckpt`` the worker loads its weights from that export
        (``model.msgpack`` path or dir) instead of the seed init — the
        deploy path (serving/deploy.py) spawns new-generation replicas
        this way."""
        port = _free_port()
        url = f"http://{self.host}:{port}"
        cmd = [
            sys.executable, "-m", "ml_trainer_tpu.serving.fleet",
            "--worker", "--name", name, "--role", role,
            "--host", self.host, "--port", str(port),
            "--model", self.model_name, "--max-len", str(self.max_len),
            "--max-batch", str(self.max_batch),
            "--max-queue", str(self.max_queue),
            "--kv-page-size", str(self.kv_page_size),
            "--kv-pages", str(self.kv_pages),
            "--seed", str(self.seed),
            "--prefill-chunk", str(self.prefill_chunk),
        ]
        if ckpt:
            cmd += ["--ckpt", ckpt]
        if not self.prefix_cache:
            cmd.append("--no-prefix-cache")
        log_path = os.path.join(self.log_dir, f"{name}.log")
        log_file = open(log_path, "w")
        env = self._worker_env()
        # Per-worker JSONL sink isolation (telemetry/export.py): a
        # shared ML_TRAINER_TPU_METRICS_JSONL path gains a `.{name}`
        # suffix in each worker, so N processes never interleave lines
        # into one file.
        env["ML_TRAINER_TPU_METRICS_WORKER"] = name
        proc = subprocess.Popen(
            cmd, env=env,
            stdout=log_file, stderr=subprocess.STDOUT,
        )
        log_file.close()  # the child holds its own descriptor
        deadline = time.monotonic() + self.spawn_timeout
        last_err: Optional[Exception] = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fleet worker '{name}' exited rc={proc.returncode} "
                    f"before readiness; log: {log_path}"
                )
            try:
                remote = RemoteServer(
                    url, proc=proc, name=name,
                    stream_timeout=self.stream_timeout,
                    log_path=log_path,
                )
                self.replicas[name] = remote
                self._log.info(
                    "fleet_spawn", name=name, role=role, url=url,
                    pid=remote.pid, ckpt=ckpt,
                )
                return remote
            except Exception as e:
                last_err = e
                time.sleep(0.1)
        proc.kill()
        raise RuntimeError(
            f"fleet worker '{name}' not ready after "
            f"{self.spawn_timeout}s ({last_err}); log: {log_path}"
        )

    def start(self) -> "Fleet":
        for role in self.roles:
            self.spawn(self._next_name(role), role)
        return self

    def factory(self, role: str) -> RemoteServer:
        """Autoscaler ``server_factory``: every scale-up (and every
        replace-dead repair) spawns a REAL process."""
        return self.spawn(self._next_name(role), role)

    def deploy_factory(self, ckpt: str):
        """A ``server_factory`` bound to a checkpoint: new-generation
        replicas for ``Router.deploy`` load their weights from ``ckpt``
        (and share the fleet's on-disk compile cache, so a deploy is
        not a recompile storm)."""
        def spawn(role: str) -> RemoteServer:
            return self.spawn(self._next_name(role), role, ckpt=ckpt)

        return spawn

    def kill(self, name: str) -> None:
        """SIGKILL one replica process directly (chaos harness)."""
        self.replicas[name].kill_process()

    def stop(self) -> None:
        for remote in self.replicas.values():
            try:
                remote.close()
            except Exception:
                pass
        self.replicas.clear()

    def make_router(self, **router_kwargs):
        """Build a :class:`Router` over the spawned fleet.  The router
        owns the RemoteServers (``close`` shuts the processes down) and
        polls health over HTTP via ``replica_urls``."""
        from ml_trainer_tpu.serving.router import Router

        router_kwargs.setdefault("own_servers", True)
        router = Router(
            replicas=dict(self.replicas),
            replica_urls={n: r.url for n, r in self.replicas.items()},
            **router_kwargs,
        )
        # Router.deploy spawns new-generation workers through this
        # launcher's checkpoint-loading factory.
        router.fleet = self
        return router


# -- worker entry ---------------------------------------------------------


def _worker_main(argv: Optional[List[str]] = None) -> int:
    """``python -m ml_trainer_tpu.serving.fleet --worker ...`` — build
    the model deterministically from the seed, serve HTTP, block until
    killed or ``/admin/shutdown``."""
    import argparse

    parser = argparse.ArgumentParser(prog="ml_trainer_tpu.serving.fleet")
    parser.add_argument("--worker", action="store_true", required=True)
    parser.add_argument("--name", default="replica")
    parser.add_argument("--role", default="both")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--model", default="gpt2_tiny")
    parser.add_argument("--max-len", type=int, default=256)
    parser.add_argument("--max-batch", type=int, default=4)
    parser.add_argument("--max-queue", type=int, default=64)
    parser.add_argument("--kv-page-size", type=int, default=16)
    parser.add_argument("--kv-pages", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--prefill-chunk", type=int, default=0)
    parser.add_argument("--no-prefix-cache", action="store_true")
    parser.add_argument("--ckpt", default=None,
                        help="load weights from this model export "
                        "(model.msgpack path or dir) instead of the "
                        "seed init — the deploy path")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    # The fleet's shared on-disk compile cache is JAX_COMPILATION_CACHE_DIR
    # (Fleet._worker_env); gpt2_tiny's CPU compiles are under the default
    # one-second threshold, so cache every program.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving.api import Server
    from ml_trainer_tpu.telemetry import compile_watch

    compile_watch.install()
    model = get_model(args.model, max_len=args.max_len)
    if args.ckpt:
        from ml_trainer_tpu.checkpoint import load_model_variables

        variables = load_model_variables(args.ckpt)
    else:
        variables = model.init(
            {"params": jax.random.PRNGKey(args.seed)},
            np.zeros((1, 8), np.int32), train=False,
        )
    server = Server(
        model, variables, max_batch=args.max_batch,
        max_queue=args.max_queue, kv_page_size=args.kv_page_size,
        kv_pages=args.kv_pages, role=args.role,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=not args.no_prefix_cache,
    )
    server.transport = "http"  # /admin/shutdown may os._exit this process
    server.name = args.name    # trace lanes / accept lines carry this
    host, port = server.serve_http(args.host, args.port)
    print(
        "FLEET_WORKER_READY "
        + json.dumps({
            "name": args.name, "url": f"http://{host}:{port}",
            "pid": os.getpid(), "role": args.role,
        }),
        flush=True,
    )
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(_worker_main())
