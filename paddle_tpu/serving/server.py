"""Stdlib HTTP front end for the serving engine (POST /generate).

Same shape as observability/serve.py's MetricsServer: one
ThreadingHTTPServer + daemon threads, no third-party web stack. The server
owns the engine loop thread — handler threads only submit requests and
block on the request's completion event, so concurrent clients are batched
CONTINUOUSLY by the single engine loop rather than serialized.

  POST /generate   {"prompt": [int, ...], "max_new_tokens": 16,
                    "temperature": 0.0, "eos_token_id": null}
               ->  {"request_id", "output_tokens", "finish_reason",
                    "telemetry": {queue_s, ttft_s, decode_tok_s, ...}}
                   With "stream": true the response is chunked
                   transfer-encoding NDJSON: one {"request_id", "tokens",
                   "done": false} line per fetched token batch, then a
                   final {"done": true, "finish_reason", "telemetry"} line.
                   The engine fetches a tick's tokens under the NEXT
                   tick's programs (engine._fetch), so a streamed token
                   reaches its reader one tick after the step that
                   sampled it, at the device's pace and not the host's;
                   an eos is seen as late, and costs the one token the
                   step after it decoded, which is dropped. Neither
                   changes a result: greedy tokens and finish reasons
                   are what a fetch in the same tick gave, and a request
                   is `finished` only with every token in its output.
                   A client disconnect cancels the request (its slot and
                   KV reservation return to the pool immediately).
  POST /kv/export  {"tokens": [...]} -> NDJSON: one line per resident
                   full prompt block (chain digest + base64 page bytes)
  POST /kv/ingest  that NDJSON -> {"imported", "dedup", "rejected",
                   "skipped", "bytes"}; chain-hash verified, idempotent
                   (disaggregated prefill->decode streaming + live KV
                   migration ride this wire)
  GET  /stats      engine + KV-pool occupancy snapshot (JSON), taken in
                   ONE engine-lock acquisition so concurrent streaming
                   never yields a torn scrape
  GET  /metrics    the process-wide metrics registry as Prometheus text
                   (observability/serve.py renders it) — TTFT/TPOT/queue
                   histograms, goodput/shed counters, KV-pool gauges
  GET  /healthz    engine health snapshot: 200 {"ok": true, status,
                   steps, last_tick_age_s, ...} / 503 when the engine
                   loop is dead, a serving anomaly fired recently, or
                   the engine has work but hasn't ticked (stale) —
                   load-balancer semantics, body says why

Every response carries the request's own telemetry (queue time, TTFT,
steady-state decode tokens/s); the aggregate gauges/histograms live in the
observability metrics registry (serving_* metrics, always on). With
FLAGS_serving_metrics_port > 0 the same /metrics + training-side /healthz
are ALSO served on a dedicated port (one scrape target per concern).
"""
from __future__ import annotations

import base64
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs

from ..core.flags import define_flag, get_flag
from ..observability import serve as _obs_serve
from . import observability as _sobs  # noqa: F401 — defines the flags
from .engine import QueueFullError

define_flag("serving_port", 0,
            "Port for the serving HTTP front end (POST /generate); 0 binds "
            "an ephemeral port.")
define_flag("serving_request_timeout_s", 300.0,
            "Per-request wall-clock cap for POST /generate before the "
            "server answers 504.")


# -------------------------------------------- KV-block wire format
# One NDJSON line per streamed block, chain order:
#   {"digest": hex, "prev": hex, "tokens": [int, ...],
#    "layers": [[k_b64, v_b64], ...]}
# — exactly engine.export_kv_blocks()'s records with the raw page bytes
# base64'd. The receiver re-derives every digest from (prev, tokens)
# before admitting anything, so a corrupted or mislabeled line is
# rejected rather than poisoning the prefix cache.

def kv_wire_encode(records) -> bytes:
    lines = [json.dumps({
        "digest": r["digest"], "prev": r["prev"], "tokens": r["tokens"],
        "layers": [[base64.b64encode(k).decode("ascii"),
                    base64.b64encode(v).decode("ascii")]
                   for k, v in r["layers"]],
    }) for r in records]
    return ("\n".join(lines) + "\n").encode() if lines else b""


def kv_wire_decode(body: bytes):
    records = []
    for line in body.splitlines():
        if not line.strip():
            continue
        o = json.loads(line)
        o["layers"] = [(base64.b64decode(k), base64.b64decode(v))
                       for k, v in o["layers"]]
        records.append(o)
    return records


class _Handler(BaseHTTPRequestHandler):
    server_version = "paddle_tpu_serving/1.0"
    # chunked transfer-encoding (streaming) requires HTTP/1.1; every
    # non-stream reply carries Content-Length so keep-alive stays valid
    protocol_version = "HTTP/1.1"

    @property
    def _srv(self):
        return self.server._serving_server  # type: ignore[attr-defined]

    def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        path = self.path.split("?", 1)[0]
        if path in ("/kv/export", "/kv/ingest"):
            self._kv_transfer(path)
            return
        if path != "/generate":
            self._reply(404, {"error": "not found"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            prompt = body.get("prompt")
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int) for t in prompt)):
                self._reply(400, {"error": "prompt must be a non-empty "
                                           "list of token ids"})
                return
            stream = bool(body.get("stream", False))
            req = self._srv.engine.submit(
                prompt,
                max_new_tokens=int(body.get("max_new_tokens", 16)),
                temperature=float(body.get("temperature", 0.0)),
                eos_token_id=body.get("eos_token_id"),
                tier=str(body.get("tier", "default")),
                prefill_only=bool(body.get("prefill_only", False)))
        except QueueFullError as e:
            # honest load shedding: tell the client WHEN to come back
            # instead of queueing without bound or failing opaquely
            self._reply(503, {"error": str(e),
                              "queue_depth": e.depth,
                              "queue_limit": e.limit,
                              "retry_after_s": e.retry_after_s},
                        headers={"Retry-After":
                                 str(max(1, int(round(e.retry_after_s))))})
            return
        except ValueError as e:
            self._reply(400, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 — malformed JSON etc.
            self._reply(400, {"error": f"{type(e).__name__}: {e}"})
            return
        timeout = float(get_flag("serving_request_timeout_s"))
        if stream:
            self._stream(req, timeout)
            return
        if not req.wait(timeout):
            # evict the abandoned request so its slot and worst-case KV
            # reservation go back to the pool instead of decoding for a
            # client that already gave up
            cancelled = self._srv.engine.cancel(req, reason="timeout")
            self._reply(504, {"error": "generation timed out",
                              "request_id": req.request_id,
                              "cancelled": cancelled})
            return
        self._reply(200, {
            "request_id": req.request_id,
            "output_tokens": req.output_tokens,
            "finish_reason": req.finish_reason,
            "telemetry": req.telemetry(),
        })

    def _kv_transfer(self, path: str) -> None:
        """Block-transfer wire for disaggregated serving / live migration.

          POST /kv/export  {"tokens": [int, ...]}
                       ->  NDJSON, one line per RESIDENT full prompt
                           block (chain order, base64 page payloads)
          POST /kv/ingest  that NDJSON body
                       ->  {"imported", "dedup", "rejected", "skipped",
                            "bytes"} — chain-hash verified, idempotent
        """
        try:
            length = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(length)
            if path == "/kv/export":
                body = json.loads(raw or b"{}")
                tokens = body.get("tokens")
                if (not isinstance(tokens, list)
                        or not all(isinstance(t, int) for t in tokens)):
                    self._reply(400, {"error": "tokens must be a list of "
                                               "token ids"})
                    return
                recs = self._srv.engine.export_kv_blocks(tokens)
                self._reply_raw(200, kv_wire_encode(recs),
                                "application/x-ndjson")
            else:
                stats = self._srv.engine.ingest_kv_blocks(
                    kv_wire_decode(raw))
                self._reply(200, stats)
        except Exception as e:  # noqa: BLE001 — malformed payloads etc.
            self._reply(400, {"error": f"{type(e).__name__}: {e}"})

    def _stream(self, req, timeout: float) -> None:
        """Chunked NDJSON: one line per engine fetch with the newly
        materialized tokens, a final line with the finish reason and
        telemetry. The engine pulses req's progress event at every fetch
        (a tick after the step that sampled the tokens); snapshots are
        taken under the engine lock so a line never shows tokens past an
        eos truncation. A broken pipe
        (client gone) cancels the request so it stops consuming slots."""
        import time as _time

        engine = self._srv.engine
        deadline = _time.monotonic() + timeout
        sent = 0
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            while True:
                req._progress.clear()
                toks, state, reason = engine.snapshot_output(req)
                if len(toks) > sent:
                    self._chunk({"request_id": req.request_id,
                                 "tokens": toks[sent:], "done": False})
                    sent = len(toks)
                if state == "finished":
                    self._chunk({"request_id": req.request_id,
                                 "done": True, "finish_reason": reason,
                                 "telemetry": req.telemetry()})
                    break
                if _time.monotonic() > deadline:
                    engine.cancel(req, reason="timeout")
                    self._chunk({"request_id": req.request_id,
                                 "done": True, "finish_reason": "timeout"})
                    break
                req.wait_progress(timeout=0.25)
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            engine.cancel(req, reason="disconnect")

    def _chunk(self, obj) -> None:
        line = json.dumps(obj).encode() + b"\n"
        self.wfile.write(f"{len(line):x}\r\n".encode() + line + b"\r\n")
        self.wfile.flush()

    def do_GET(self):  # noqa: N802
        path = self.path.split("?", 1)[0]
        if path == "/stats":
            # one lock acquisition inside stats(): the whole snapshot is
            # consistent even while streaming requests mutate the
            # scheduler between ticks
            self._reply(200, self._srv.engine.stats())
        elif path == "/metrics":
            self._reply_raw(200, _obs_serve.metrics_body(),
                            "text/plain; version=0.0.4; charset=utf-8")
        elif path in ("/healthz", "/health"):
            snap = self._srv.engine.obs.health_snapshot(
                loop_alive=self._srv.loop_alive())
            self._reply(200 if snap["ok"] else 503, snap)
        else:
            self._reply(404, {"error": "not found"})

    def _reply(self, code: int, obj, headers=None) -> None:
        self._reply_raw(code, json.dumps(obj).encode(), "application/json",
                        headers=headers)

    def _reply_raw(self, code: int, body: bytes, ctype: str,
                   headers=None) -> None:
        try:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def log_message(self, fmt, *args):  # requests must not spam stderr
        pass


class _FleetHandler(_Handler):
    """Fleet front end: same wire protocol as _Handler, but requests are
    routed across N replicas by a FleetRouter — replica death, hedging
    and drains are invisible to the client beyond the telemetry block.

      POST /generate   as _Handler (no streaming: a fleet request may
                       migrate replicas mid-flight, so tokens are only
                       final once the request settles)
      POST /drain      {"replica": "replica-0"} — rolling-restart drain;
                       /resume undoes it
      GET  /healthz    200 while ANY replica can take traffic; body
                       carries every replica's own health snapshot
                       (including `draining`) + breaker state
      GET  /stats      router + per-replica engine snapshots
    """

    @property
    def _router(self):
        return self._srv.router  # type: ignore[attr-defined]

    def do_POST(self):  # noqa: N802
        path = self.path.split("?", 1)[0]
        if path in ("/drain", "/resume"):
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                rid = str(body.get("replica", ""))
                if rid not in self._router.replicas:
                    self._reply(404, {"error": f"unknown replica {rid!r}"})
                    return
                if path == "/drain":
                    self._router.drain(rid)
                    self._reply(200, {"replica": rid, "status": "draining",
                                      "drained": self._router.drained(rid)})
                else:
                    self._router.resume(rid)
                    self._reply(200, {"replica": rid, "status": "ok"})
            except Exception as e:  # noqa: BLE001 — malformed JSON etc.
                self._reply(400, {"error": f"{type(e).__name__}: {e}"})
            return
        if path != "/generate":
            self._reply(404, {"error": "not found"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            prompt = body.get("prompt")
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int) for t in prompt)):
                self._reply(400, {"error": "prompt must be a non-empty "
                                           "list of token ids"})
                return
            freq = self._router.submit(
                prompt,
                max_new_tokens=int(body.get("max_new_tokens", 16)),
                temperature=float(body.get("temperature", 0.0)),
                eos_token_id=body.get("eos_token_id"),
                tier=str(body.get("tier", "default")))
        except QueueFullError as e:
            self._reply(503, {"error": str(e),
                              "queue_depth": e.depth,
                              "queue_limit": e.limit,
                              "retry_after_s": e.retry_after_s},
                        headers={"Retry-After":
                                 str(max(1, int(round(e.retry_after_s))))})
            return
        except ValueError as e:
            self._reply(400, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 — malformed JSON etc.
            self._reply(400, {"error": f"{type(e).__name__}: {e}"})
            return
        timeout = float(get_flag("serving_request_timeout_s"))
        if not freq.wait(timeout):
            self._reply(504, {"error": "generation timed out",
                              "request_id": freq.request_id})
            return
        self._reply(200, {
            "request_id": freq.request_id,
            "output_tokens": freq.output_tokens,
            "finish_reason": freq.finish_reason,
            "fleet": {"redispatches": freq.redispatches,
                      "hedged": freq.hedged},
        })

    def do_GET(self):  # noqa: N802
        split = self.path.split("?", 1)
        path = split[0]
        if path == "/stats":
            self._reply(200, self._router.stats())
        elif path == "/metrics":
            # fleet_slo_seconds gauges are rollups over the attempt
            # histograms: recompute at scrape time so they are current
            self._router.obs.publish_rollups()
            self._reply_raw(200, _obs_serve.metrics_body(),
                            "text/plain; version=0.0.4; charset=utf-8")
        elif path in ("/healthz", "/health"):
            snap = self._router.health()
            self._reply(200 if snap["ok"] else 503, snap)
        elif path == "/trace":
            query = parse_qs(split[1]) if len(split) > 1 else {}
            rid = (query.get("id") or [None])[0]
            if not rid:
                self._reply(400, {"error": "usage: /trace?id=<request_id>"})
                return
            payload = self._router.obs.trace_payload(rid)
            if payload is None:
                self._reply(404, {
                    "error": f"no merged trace for request {rid!r} "
                             "(unknown id, evicted from the settled "
                             "ring, or FLAGS_metrics was off at submit)"})
                return
            self._reply(200, payload)
        else:
            self._reply(404, {"error": "not found"})


class FleetServer:
    """HTTP front end over a FleetRouter. The router owns the replica
    engine loops and the failure monitor; this server only binds the
    socket and starts/stops the router alongside it."""

    def __init__(self, router, port: Optional[int] = None,
                 host: str = "127.0.0.1"):
        self.router = router
        if port is None:
            port = int(get_flag("serving_port"))
        self._httpd = ThreadingHTTPServer((host, int(port)), _FleetHandler)
        self._httpd.daemon_threads = True
        self._httpd._serving_server = self  # type: ignore[attr-defined]
        self.port = int(self._httpd.server_address[1])
        self.host = host
        self.router.start()
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.25},
            name="fleet-http", daemon=True)
        self._http_thread.start()

    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._http_thread.join(timeout=5)
        self.router.stop()

    def __repr__(self):  # pragma: no cover
        return f"FleetServer(port={self.port})"


class ServingServer:
    """HTTP server + the engine loop thread. The loop runs engine ticks
    while there is work and idles (short sleep) otherwise; handler threads
    never touch the device."""

    def __init__(self, engine, port: Optional[int] = None,
                 host: str = "127.0.0.1", idle_sleep_s: float = 0.002):
        self.engine = engine
        if port is None:
            port = int(get_flag("serving_port"))
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd._serving_server = self  # type: ignore[attr-defined]
        self.port = int(self._httpd.server_address[1])
        self.host = host
        self._idle_sleep_s = float(idle_sleep_s)
        # optional dedicated observability port (FLAGS_serving_metrics_
        # port, defined in serving/observability.py): the process-wide
        # /metrics + training-style /healthz via observability/serve.py.
        # Bind failure degrades to None — never a dead serving process.
        self.metrics_server = None
        mp = int(get_flag("serving_metrics_port"))
        if mp > 0:
            try:
                self.metrics_server = _obs_serve.MetricsServer(mp)
            except OSError:
                pass
        self._stop = threading.Event()
        self._loop = threading.Thread(target=self._run_loop,
                                      name="serving-engine", daemon=True)
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.25},
            name="serving-http", daemon=True)
        self._loop.start()
        self._http_thread.start()

    def _run_loop(self) -> None:
        while not self._stop.is_set():
            if self.engine.sched.has_work():
                self.engine.step()
            else:
                time.sleep(self._idle_sleep_s)

    def loop_alive(self) -> bool:
        return self._loop.is_alive()

    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        self._stop.set()
        self._loop.join(timeout=10)
        self._httpd.shutdown()
        self._httpd.server_close()
        self._http_thread.join(timeout=5)
        if self.metrics_server is not None:
            try:
                self.metrics_server.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            self.metrics_server = None

    def __repr__(self):  # pragma: no cover
        return f"ServingServer(port={self.port})"
