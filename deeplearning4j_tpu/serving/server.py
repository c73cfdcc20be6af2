"""HTTP inference endpoint over the micro-batched engine.

Same stdlib ThreadingHTTPServer + JSON/Base64-f32 transport as
clustering/knn_server.py (the reference's NearestNeighborsServer analog);
each POST /predict rides the micro-batcher, so concurrent HTTP clients are
coalesced into shared device calls. Wire format in docs/SERVING.md.

Endpoints:
  POST /predict  {"ndarray": {shape, data}, "deadline_ms"?} → {"ndarray": ...}
  POST /warmup   {"input_shape": [...], "max_batch"}        → {"buckets": [...]}
  POST /admin/swap {"checkpoint": path, "version"?}         → {"version": n}
  POST /admin/profile {"dir": d, "seconds"?}                → timed jax.profiler capture
  GET  /stats                                               → engine+batcher stats
  GET  /metrics                                             → Prometheus text
  GET  /healthz                                             → {"status": ...}
  GET  /trace                                               → span ring buffer (Chrome JSON)
  GET  /programs                                            → compiled-program cost table

/predict and /generate responses carry ``x-model-version`` (the serving
weights' hot-swap version, docs/ONLINE_LEARNING.md); 409 with type
``weight_mismatch`` rejects an incompatible /admin/swap candidate before
the live engines are touched.

Error contract (docs/FAULT_TOLERANCE.md): every error body is structured —
``{"error": {"type": ..., "message": ...}}`` — and the status code
classifies it: **400** malformed payload (bad JSON, missing ``ndarray``,
wrong rank/feature width), **429** queue full (shed immediately, the
handler thread never blocks on a full queue), **503** draining/stopped,
**504** request deadline expired (answered without riding a device call),
**500** engine faults only. ``/healthz`` reports ``ok`` | ``degraded``
(queue ≥ 80% full or a recent engine fault) | ``draining`` (status 503, so
load balancers pull the instance while in-flight work flushes).
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

from deeplearning4j_tpu.clustering.knn_server import (
    ndarray_from_b64, ndarray_to_b64)
from deeplearning4j_tpu.monitor import get_registry, trace
from deeplearning4j_tpu.monitor import profiling, tracing
from deeplearning4j_tpu.monitor.slo import BurnRateSLO
from deeplearning4j_tpu.resilience.errors import (
    BatcherStoppedError, CorruptCheckpointError, DeadlineExceededError,
    InjectedFaultError, ServerOverloadedError, WeightSwapError)
from deeplearning4j_tpu.serving.batcher import MicroBatcher
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.serving.kv import KVMigrateError

_KNOWN_PATHS = ("/predict", "/generate", "/warmup", "/stats", "/metrics",
                "/healthz", "/chaos", "/admin/swap", "/trace", "/programs",
                "/admin/profile", "/train/diagnostics", "/kv/export",
                "/kv/import", "/requests")


def _http_metrics():
    reg = get_registry()
    return (reg.counter("dl4jtpu_http_requests_total",
                        "HTTP requests served by the inference server.",
                        ("path",)),
            reg.histogram("dl4jtpu_http_request_seconds",
                          "Wall seconds per HTTP request, handler-inclusive.",
                          ("path",)))


class BadRequestError(ValueError):
    """Client-side payload problem → HTTP 400 (never 500)."""


class _Handler(BaseHTTPRequestHandler):
    # HTTP/1.1 enables keep-alive: clients reuse one TCP connection across
    # requests instead of paying connect + slow-start per call. Safe here
    # because every response path (_json/_error/_text) sets an exact
    # Content-Length, which 1.1 persistence requires.
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    @property
    def _rid(self):
        # router-assigned x-request-id: echoed on every response and into
        # error bodies + trace spans, so one grep follows a request across
        # the router, both halves of a hedged pair, and the replica.
        # Direct-to-replica requests with no id get one MINTED here, so
        # they're never anonymous in the journal or the traces; the mint
        # is cached against this request's header object (fresh per
        # request even on a keep-alive connection), so every response
        # header and journal record of one request agrees.
        rid = self.headers.get("x-request-id")
        if rid:
            return rid
        minted = getattr(self, "_rid_minted", None)
        if minted is None or minted[0] is not self.headers:
            minted = (self.headers, self.server.inference.mint_rid())
            self._rid_minted = minted
        return minted[1]

    def _json(self, obj, code=200, extra_headers=None):
        data = json.dumps(obj).encode()
        self._status = code
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        if self._rid:
            self.send_header("x-request-id", self._rid)
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _error(self, code: int, err_type: str, message: str):
        err = {"type": err_type, "message": message}
        if self._rid:
            err["request_id"] = self._rid
        self._json({"error": err}, code)

    def _text(self, body: str, content_type: str, code=200):
        data = body.encode()
        self._status = code
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        if self._rid:
            self.send_header("x-request-id", self._rid)
        self.end_headers()
        self.wfile.write(data)

    def _observed(self, path, fn):
        # per-path request count + latency; unknown paths share one series
        # so a URL-probing client can't mint unbounded label values
        counter, hist = _http_metrics()
        label = path if path in _KNOWN_PATHS else "other"
        # router-minted trace context: installed thread-local for the whole
        # handler, so this request's spans (http_request and, via the
        # batcher's queue item, the engine's bucket/pad/device/readback)
        # all carry the fleet trace_id
        ctx = tracing.TraceContext.from_header(
            self.headers.get("x-trace-context"))
        self._status = 200
        t0 = time.perf_counter()
        try:
            with tracing.trace_context(ctx):
                with trace.span("http_request", path=label,
                                request_id=self._rid or ""):
                    fn()
        finally:
            counter.labels(path=label).inc()
            hist.labels(path=label).observe(time.perf_counter() - t0)
            self.server.inference.note_response(label, self._status)

    def do_GET(self):
        srv = self.server.inference
        path = urlparse(self.path).path

        def handle():
            if path == "/stats":
                self._json(srv.stats())
            elif path == "/healthz":
                info = srv.health_info()
                self._json(info,
                           503 if info["status"] == "draining" else 200)
            elif path == "/metrics":
                self._text(get_registry().render(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/trace":
                # this process's span ring buffer as one Chrome trace-event
                # document — what monitor/collect.py pulls per process
                self._json(trace.export())
            elif path == "/requests":
                # the wide-event request journal (predict + decode rings
                # merged on one timeline) — what collect_requests pulls
                # per replica; ?n= bounds the tail
                q = parse_qs(urlparse(self.path).query)
                n = q.get("n", [None])[0]
                try:
                    n = None if n is None else int(n)
                except ValueError:
                    self._error(400, "bad_request",
                                f"n must be an integer, got {n!r}")
                    return
                self._json(srv.request_journal(n))
            elif path == "/programs":
                from deeplearning4j_tpu.exec.programs import get_programs
                self._json({"programs": get_programs().entries()})
            elif path == "/train/diagnostics":
                # the flight recorder's black box: recent per-layer step
                # records + active anomalies (monitor/flight.py)
                if srv.flight_recorder is None:
                    self._error(404, "not_found",
                                "no flight recorder attached to this server")
                else:
                    self._json(srv.flight_recorder.diagnostics())
            else:
                self._error(404, "not_found", f"no such path: {path}")

        self._observed(path, handle)

    def do_POST(self):
        srv = self.server.inference
        path = urlparse(self.path).path
        n = int(self.headers.get("Content-Length", 0))
        try:
            payload = json.loads(self.rfile.read(n).decode())
            if not isinstance(payload, dict):
                raise ValueError("payload must be a JSON object")
        except Exception as e:  # noqa: BLE001 — client sent junk
            self._error(400, "bad_request", f"bad json: {e}")
            return

        def handle():
            try:
                if path in ("/predict", "/generate") \
                        and srv.fault_injector is not None:
                    # chaos harness hook: injected latency rides the handler
                    # thread; injected faults surface as the configured 5xx
                    srv.fault_injector.maybe_inject(path)
                if path == "/predict":
                    self._predict(srv, payload)
                elif path == "/generate":
                    self._generate(srv, payload)
                elif path == "/chaos":
                    if srv.fault_injector is None:
                        self._error(404, "not_found",
                                    "chaos injection not enabled "
                                    "on this server")
                    else:
                        srv.fault_injector.configure(**payload)
                        self._json({"chaos": srv.fault_injector.describe()})
                elif path == "/kv/export":
                    self._kv_export(srv, payload)
                elif path == "/kv/import":
                    self._kv_import(srv, payload)
                elif path == "/admin/swap":
                    self._admin_swap(srv, payload)
                elif path == "/admin/profile":
                    self._admin_profile(srv, payload)
                elif path == "/warmup":
                    try:
                        shape = payload["input_shape"]
                    except KeyError:
                        raise BadRequestError(
                            "payload missing 'input_shape'") from None
                    shapes = ([tuple(s) for s in shape]
                              if shape and isinstance(shape[0], list)
                              else tuple(shape))
                    buckets = srv.engine.warmup(
                        shapes, max_batch=payload.get("max_batch"))
                    self._json({"buckets": buckets,
                                "seconds": srv.engine.warmup_seconds})
                else:
                    self._error(404, "not_found", f"no such path: {path}")
            except BadRequestError as e:
                self._error(400, "bad_request", str(e))
            except WeightSwapError as e:
                # structured rejection: the live engines were never touched
                self._error(409, "weight_mismatch", str(e))
            except KVMigrateError as e:
                # same discipline: validation rejected the payload before
                # the destination pool was touched
                self._error(409, "kv_migrate_rejected", str(e))
            except (CorruptCheckpointError, FileNotFoundError) as e:
                self._error(400, "bad_checkpoint", str(e))
            except InjectedFaultError as e:
                self._error(e.code, "injected_fault", str(e))
            except ServerOverloadedError as e:
                self._error(429, "overloaded", str(e))
            except BatcherStoppedError as e:
                self._error(503, "draining", str(e))
            except DeadlineExceededError as e:
                self._error(504, "deadline_exceeded", str(e))
            except Exception as e:  # noqa: BLE001 — engine fault: 500
                srv.note_engine_error(e)
                self._error(500, "internal",
                            f"{type(e).__name__}: {e}")

        self._observed(path, handle)

    def _admin_swap(self, srv, payload):
        """POST /admin/swap {"checkpoint": path, "version"?: int} — load a
        checkpoint's weights and hot-swap them into the live engines (the
        online-learning deploy path; see docs/ONLINE_LEARNING.md)."""
        try:
            ck = payload["checkpoint"]
        except KeyError:
            raise BadRequestError("payload missing 'checkpoint'") from None
        version = payload.get("version")
        if version is not None:
            try:
                version = int(version)
            except (TypeError, ValueError):
                raise BadRequestError(
                    f"version must be an int, got {version!r}") from None
        v = srv.swap_checkpoint(ck, version=version)
        self._json({"swapped": True, "version": v,
                    "checkpoint": str(ck),
                    "compiled_programs": srv.engine.trace_count})

    def _admin_profile(self, srv, payload):
        """POST /admin/profile {"dir": path, "seconds"?: float} — wrap the
        next N seconds of live traffic in ``jax.profiler.trace``; one
        session at a time per process (409 while one runs)."""
        if profiling.profile_status()["profiling"]:
            self._error(409, "profile_busy",
                        "a profiling session is already running")
            return
        try:
            out = profiling.start_profile(
                payload.get("dir", ""),
                seconds=float(payload.get("seconds", 5.0)))
        except (TypeError, ValueError) as e:
            raise BadRequestError(str(e)) from None
        except RuntimeError as e:
            self._error(503, "profiler_unavailable", str(e))
            return
        self._json(out)

    def _predict(self, srv, payload):
        try:
            raw = payload["ndarray"]
        except KeyError:
            raise BadRequestError("payload missing 'ndarray'") from None
        try:
            x = ndarray_from_b64(raw)
        except Exception as e:  # noqa: BLE001 — undecodable client bytes
            raise BadRequestError(f"undecodable ndarray: {e}") from None
        deadline_ms = payload.get("deadline_ms", srv.request_timeout_ms)
        if deadline_ms is not None:
            try:
                deadline_ms = float(deadline_ms)
            except (TypeError, ValueError):
                raise BadRequestError(
                    f"deadline_ms must be a number, got "
                    f"{payload.get('deadline_ms')!r}") from None
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None, :]
        srv.validate_features(x)
        if srv.request_mirror is not None:
            try:
                # shadow-evaluation tap (online/gate.TrafficMirror): a copy
                # of real traffic, never allowed to fail a real request
                srv.request_mirror(x)
            except Exception:   # noqa: BLE001 — mirror is best-effort
                pass
        # block=False: a full queue answers 429 NOW — the handler thread is
        # never parked on backpressure while the client waits
        fut = srv.batcher.submit(
            x, deadline_ms=deadline_ms, block=False,
            request_id=self._rid,
            tenant=self.headers.get("x-tenant", "default"),
            priority=self.headers.get("x-priority", "normal"))
        out = fut.result()
        if squeeze:
            out = out[0]
        # version read at response time: a request racing a swap may report
        # the new version for an answer computed on the old weights — the
        # benign direction (versions only move forward; see the docs)
        self._json({"ndarray": ndarray_to_b64(out)},
                   extra_headers={
                       "x-model-version": str(srv.engine.model_version)})

    def _kv_gate(self, srv):
        """Both migration endpoints require a paged decode engine with a
        prefix cache (the chain index IS the migration unit)."""
        dec = srv.decode_engine
        if dec is None or getattr(dec, "_prefix", None) is None:
            self._error(404, "not_found",
                        "KV migration requires a paged decode engine with "
                        "prefix_cache on this server")
            return None
        return dec

    def _kv_export(self, srv, payload):
        """POST /kv/export {"tokens": [...]} — serialize the cached block
        chain covering the prompt's full blocks (disaggregation: the
        prefill replica's half of a handoff)."""
        dec = self._kv_gate(srv)
        if dec is None:
            return
        try:
            tokens = payload["tokens"]
        except KeyError:
            raise BadRequestError("payload missing 'tokens'") from None
        if (not isinstance(tokens, list)
                or not all(isinstance(t, int) for t in tokens)):
            raise BadRequestError("'tokens' must be a list of token ids")
        self._json(dec.kv_export(tokens), extra_headers={
            "x-model-version": str(dec.model_version)})

    def _kv_import(self, srv, payload):
        """POST /kv/import <export payload> — restore a migrated chain
        into this replica's pool (the decode replica's half). Envelope or
        integrity mismatches answer 409 with the pool untouched."""
        dec = self._kv_gate(srv)
        if dec is None:
            return
        self._json(dec.kv_import(payload), extra_headers={
            "x-model-version": str(dec.model_version)})

    def _generate(self, srv, payload):
        if srv.decode_engine is None:
            self._error(404, "not_found",
                        "no decode engine configured on this server")
            return
        try:
            tokens = payload["tokens"]
        except KeyError:
            raise BadRequestError("payload missing 'tokens'") from None
        if (not isinstance(tokens, list)
                or not all(isinstance(t, int) for t in tokens)):
            raise BadRequestError("'tokens' must be a list of token ids")
        try:
            out = srv.decode_engine.generate(
                tokens,
                max_new_tokens=int(payload.get("max_new_tokens", 32)),
                seed=int(payload.get("seed", 0)),
                temperature=float(payload.get("temperature", 0.0)),
                top_k=int(payload.get("top_k", 0)),
                request_id=self._rid,
                tenant=self.headers.get("x-tenant", "default"),
                priority=self.headers.get("x-priority", "normal"))
        except ValueError as e:     # capacity / id-range problems → 400
            raise BadRequestError(str(e)) from None
        self._json(out, extra_headers={
            "x-model-version": str(srv.decode_engine.model_version)})


class InferenceServer:
    """Serve a model container over HTTP through bucketed micro-batching.

        srv = InferenceServer(net, port=0).start()
        out = InferenceClient(f"http://localhost:{srv.port}").predict(x)

    ``max_queue``: bound on queued requests (beyond it: HTTP 429).
    ``request_timeout_ms``: default per-request deadline when the client
    does not send ``deadline_ms`` (None = no deadline).
    """

    _ids = itertools.count()

    def __init__(self, model, port: int = 9300, host: str = "127.0.0.1",
                 max_batch: int = 256, max_latency_ms: float = 2.0,
                 engine: Optional[InferenceEngine] = None,
                 max_queue: int = 1024,
                 request_timeout_ms: Optional[float] = None,
                 decode_engine=None, fault_injector=None,
                 health_hook=None, request_mirror=None,
                 flight_recorder=None, role: str = "mixed",
                 journal_capacity: int = 512):
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'mixed', got {role!r}")
        # disaggregation role advertised in /stats: a routing PREFERENCE
        # the fleet router reads (prefill-specialized replicas take fresh
        # prefills, decode-specialized ones take migrated chains) — the
        # server itself serves every endpoint regardless of role, so a
        # degraded fleet can always fail over across roles
        self.role = role
        self.engine = engine or InferenceEngine(model)
        # serving/decode.DecodeEngine for POST /generate (None = endpoint
        # answers 404; predict-only servers don't pay for decode slots)
        self.decode_engine = decode_engine
        # resilience/faults.ServerFaultInjector (chaos harness): when set,
        # /predict and /generate pass through it (latency / injected 5xx)
        # and POST /chaos reconfigures it live; None = no chaos surface
        self.fault_injector = fault_injector
        # health_hook: () -> {"status": ...} | None — extra health merged
        # into /healthz (the online trainer degrades serving health on a
        # stalled stream instead of dying; docs/ONLINE_LEARNING.md)
        self.health_hook = health_hook
        # request_mirror: (features ndarray) -> None — best-effort tap on
        # /predict traffic (online/gate.TrafficMirror shadow evaluation)
        self.request_mirror = request_mirror
        # flight_recorder: monitor/flight.FlightRecorder — exposes the
        # training black box at GET /train/diagnostics (None = 404) and
        # degrades /healthz while a degrading training anomaly is active
        self.flight_recorder = flight_recorder
        self.batcher = MicroBatcher(self.engine, max_batch=max_batch,
                                    max_latency_ms=max_latency_ms,
                                    max_queue=max_queue,
                                    journal_capacity=journal_capacity)
        self.request_timeout_ms = request_timeout_ms
        self._port_req = port
        self._host = host
        self._httpd = None
        self.port: Optional[int] = None
        self._draining = threading.Event()
        self.last_error: Optional[str] = None
        self._m_engine_errors = get_registry().counter(
            "dl4jtpu_serving_engine_errors_total",
            "Engine faults surfaced as HTTP 500 by the inference server.")
        # per-instance response classes: the SLI under the burn-rate SLO.
        # Labelled by server instance so a restarted replica starts with a
        # clean error budget instead of inheriting the old process-lifetime
        # counters (the registry is process-wide).
        self.id = f"server{next(InferenceServer._ids)}"
        self._m_responses = get_registry().counter(
            "dl4jtpu_http_responses_total",
            "HTTP responses by status class, per server instance.",
            ("server", "path", "class"))
        sli, bad = [], []
        for p in ("/predict", "/generate"):
            for c in ("2xx", "4xx", "5xx"):
                child = self._m_responses.labels(
                    server=self.id, path=p, **{"class": c})
                sli.append(child)
                if c == "5xx":
                    bad.append(child)
        # availability SLO over /predict + /generate: 5xx (engine faults,
        # injected chaos) burn the budget; 4xx are the client's problem.
        # Fast burn at 14.4x ≈ a sustained >14% 5xx rate over BOTH the 5m
        # and 1h windows — /healthz flips to degraded, and recovers as
        # soon as the short window clears (docs/OBSERVABILITY.md).
        self.slo = BurnRateSLO(
            f"availability:{self.id}",
            bad_fn=lambda: sum(c.value for c in bad),
            total_fn=lambda: sum(c.value for c in sli),
            objective=0.99)
        # request-id mint for direct-to-replica requests (no router, no
        # client-supplied id): pid + server instance keeps ids unique
        # across a local fleet so the merged journal never mis-joins
        self._rid_prefix = f"{os.getpid():x}-{self.id}"
        self._rid_counter = itertools.count(1)

    def mint_rid(self) -> str:
        return f"req-{self._rid_prefix}-{next(self._rid_counter):06d}"

    # --------------------------------------------------------------- health
    def note_engine_error(self, e: BaseException) -> None:
        self.last_error = f"{type(e).__name__}: {e}"
        self._m_engine_errors.inc()

    def note_response(self, path: str, code: int) -> None:
        """Count one HTTP response by status class (called by the handler
        for every request; feeds the availability SLO)."""
        try:
            cls = f"{int(code) // 100}xx"
            self._m_responses.labels(server=self.id, path=path,
                                     **{"class": cls}).inc()
        except Exception:   # noqa: BLE001 — accounting never breaks serving
            pass

    def validate_features(self, x: np.ndarray) -> None:
        """400 for wrong rank / feature width when the model's conf declares
        a fixed input type (feed-forward feature count)."""
        itype = getattr(getattr(self.engine, "model", None), "conf", None)
        itype = getattr(itype, "input_type", None)
        if itype is None or getattr(itype, "kind", None) not in (
                "ff", "cnn_flat"):
            return
        expected = itype.batch_shape(1)
        if x.ndim != len(expected) or x.shape[1:] != expected[1:]:
            raise BadRequestError(
                f"input shape {tuple(x.shape)} does not match model input "
                f"(batch, {', '.join(str(d) for d in expected[1:])})")

    def health_info(self) -> dict:
        """``{"status": ...}`` plus a ``reason`` when degraded. Degraded
        states a router acts on: ``queue_pressure`` (micro-batch queue ≥80%
        full), ``kv_pool_exhausted`` (a paged decode engine cannot claim KV
        blocks for the request at its queue head — long-prompt work should
        steer away until blocks free up) and ``decode_saturated`` (every
        DecodeEngine slot busy — new /generate work queues behind a full
        batch, so prefill-heavy traffic should steer to replicas with free
        slots)."""
        if self._draining.is_set() or self.batcher.stopping:
            return {"status": "draining"}
        st = self.batcher.stats()
        if st["queue_capacity"] and (st["queue_depth"]
                                     >= 0.8 * st["queue_capacity"]):
            return {"status": "degraded", "reason": "queue_pressure"}
        if (self.decode_engine is not None
                and getattr(self.decode_engine, "kv_exhausted", False)):
            return {"status": "degraded", "reason": "kv_pool_exhausted",
                    "kv": self.decode_engine.kv_pool_info()}
        if self.decode_engine is not None and self.decode_engine.saturated:
            return {"status": "degraded", "reason": "decode_saturated"}
        if self.health_hook is not None:
            try:
                extra = self.health_hook()
            except Exception:   # noqa: BLE001 — a broken hook can't take
                extra = None    # the whole server unhealthy
            if extra and extra.get("status") not in (None, "ok"):
                return extra
        if self.flight_recorder is not None:
            try:
                fr = self.flight_recorder.health_info()
            except Exception:   # noqa: BLE001 — telemetry can't take the
                fr = None       # whole server unhealthy
            if fr and fr.get("status") not in (None, "ok"):
                return fr
        try:
            slo = self.slo.evaluate()
        except Exception:       # noqa: BLE001 — SLO math can't break health
            slo = None
        if slo is not None and slo.fast_burn:
            return {"status": "degraded", "reason": "slo_fast_burn",
                    "slo": slo.as_dict()}
        return {"status": "ok"}

    def health(self) -> str:
        return self.health_info()["status"]

    def stats(self) -> dict:
        from deeplearning4j_tpu.exec.mesh import device_info
        out = {"engine": self.engine.stats(),
               "batcher": self.batcher.stats(),
               "device": device_info(),
               "health": self.health(),
               "role": self.role,
               "model_version": self.engine.model_version,
               "last_error": self.last_error}
        if self.decode_engine is not None:
            out["decode"] = self.decode_engine.stats()
        return out

    def request_journal(self, n: Optional[int] = None) -> dict:
        """The wide-event journal this replica serves at ``GET
        /requests?n=``: the /predict (batcher) and /generate (decode)
        rings merged onto one ``ts`` timeline, newest last."""
        logs = [self.batcher.journal]
        if self.decode_engine is not None:
            logs.append(self.decode_engine.journal)
        recs, total, dropped = [], 0, 0
        for lg in logs:
            snap = lg.snapshot()
            recs.extend(snap["records"])
            total += snap["total"]
            dropped += snap["dropped"]
        recs.sort(key=lambda r: r.get("ts") or 0.0)
        if n is not None:
            recs = recs[-n:] if n > 0 else []
        return {"server": self.id, "total": total, "dropped": dropped,
                "records": recs}

    # ------------------------------------------------------------- hot swap
    def swap_weights(self, params, state=None,
                     version: Optional[int] = None) -> int:
        """Hot-swap both engines to a same-shape weight pytree. The decode
        engine (if any) stages first and applies at its next empty step
        boundary — in-flight generations finish on the old weights — then
        /predict cuts over. Validation happens before either engine is
        touched, so a ``WeightSwapError`` leaves serving exactly as it was.
        Returns the new model version."""
        if version is None:
            version = self.engine.model_version + 1
        if self.decode_engine is not None:
            self.decode_engine.swap_weights(params, state, version=version)
        return self.engine.swap_weights(params, state, version=version)

    def swap_checkpoint(self, path, version: Optional[int] = None) -> int:
        """Load a checkpoint zip's (params, state) and hot-swap them in —
        what POST /admin/swap calls. The zip's own configuration is ignored
        (see model_serializer.load_weights), so head-only transfer-learning
        checkpoints swap into the full serving net."""
        from deeplearning4j_tpu.util import model_serializer
        params, state = model_serializer.load_weights(self.engine.model,
                                                      path)
        return self.swap_weights(params, state, version=version)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "InferenceServer":
        self.batcher.start()
        if self.decode_engine is not None:
            self.decode_engine.start()
        self._httpd = _TrackingHTTPServer((self._host, self._port_req),
                                          _Handler)
        self._httpd.inference = self
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()
        return self

    def stop(self) -> None:
        """Graceful drain: flag draining (healthz → 503, LBs pull us), let
        the batcher flush everything already queued, then close the HTTP
        listener AND every established keep-alive connection. Requests
        arriving mid-drain get fast 503s, not hangs — and clients are
        forced to redial, so a restart-in-place on the same port never
        leaves them talking to the dead server's handler threads."""
        self._draining.set()
        self.batcher.stop()
        if self.decode_engine is not None:
            self.decode_engine.stop()
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd.close_all_connections()


class _TrackingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that remembers established connections.

    ``shutdown()`` only stops the accept loop; keep-alive connections
    stay open and their daemon handler threads keep answering — after a
    graceful stop that means a permanent stream of 503s on sockets a
    freshly restarted server on the same port can never inherit. Closing
    them at stop() turns "stale connection" into a connect-level error
    the client's reconnect-once logic absorbs on its next request."""

    def __init__(self, *args, **kwargs):
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        super().__init__(*args, **kwargs)

    def get_request(self):
        sock_, addr = super().get_request()
        with self._conns_lock:
            self._conns.add(sock_)
        return sock_, addr

    def shutdown_request(self, request):
        with self._conns_lock:
            self._conns.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self) -> None:
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for sock_ in conns:
            try:
                sock_.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock_.close()
            except OSError:
                pass
