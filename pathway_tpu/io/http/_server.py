"""aiohttp-based REST ingress (parity: io/http/_server.py).

One ``PathwayWebserver`` per (host, port); multiple ``rest_connector`` routes
register handlers.  Each request: admission (``engine/serving.py`` — bounded
in-flight budget, deadline-aware queue, 429/503 rejects with Retry-After) →
assign a request id → push a deadline-stamped row into the input table (via
ConnectorSubject) → wait on a future completed by the response writer
subscribed to the result table (or failed typed by the pipeline error /
staging-shed hooks) → reply.  See docs/serving.md for the contract.
"""

from __future__ import annotations

import asyncio
import itertools
import json as _json
import threading
import time as _time
from typing import Any

from pathway_tpu.engine import serving, tracing
from pathway_tpu.engine.freshness import safe_label
from pathway_tpu.engine.metrics import MS_BUCKETS, get_registry
from pathway_tpu.engine.types import Json, Pointer, hash_values
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import schema as schema_mod
from pathway_tpu.internals.config import env_float
from pathway_tpu.internals.table import Table
from pathway_tpu.io import _utils
from pathway_tpu.io._utils import COMMIT, Reader

DEADLINE_HEADER = "X-Pathway-Deadline-Ms"
TRACEPARENT_HEADER = "traceparent"


class EndpointExamples:
    """Named request examples for endpoint documentation (reference
    _server.py:89); rendered into the OpenAPI schema's ``examples`` map."""

    def __init__(self):
        self.examples_by_id = {}

    def add_example(self, id, summary, values):
        if id in self.examples_by_id:
            raise ValueError(f"Duplicate example id: {id}")
        self.examples_by_id[id] = {"summary": summary, "value": values}
        return self

    def _openapi_description(self):
        return self.examples_by_id


class EndpointDocumentation:
    def __init__(
        self,
        *,
        summary=None,
        description=None,
        tags=None,
        method_types=None,
        examples: "EndpointExamples | None" = None,
        **kw,
    ):
        self.summary = summary
        self.description = description
        self.tags = tags
        self.method_types = method_types
        self.examples = examples


class PathwayWebserver:
    """Shared aiohttp server; routes added by rest_connector."""

    def __init__(self, host: str, port: int, with_schema_endpoint: bool = False, with_cors: bool = False):
        self.host = host
        self.port = port
        self._routes: dict[tuple[str, str], Any] = {}
        self._route_docs: dict[str, dict] = {}  # route -> openapi path item
        self.with_schema_endpoint = with_schema_endpoint
        self._started = False
        self._loop: asyncio.AbstractEventLoop | None = None
        self._ready = threading.Event()
        self._startup_error: BaseException | None = None

    def _add_route(
        self, route: str, methods: list[str], handler, *, schema=None, documentation=None
    ) -> None:
        for m in methods:
            self._routes[(m.upper(), route)] = handler
        self._route_docs[route] = self._openapi_path_item(
            methods, schema, documentation
        )

    @staticmethod
    def _openapi_path_item(methods, schema, documentation) -> dict:
        """OpenAPI v3 path item for one route (the reference's schema
        endpoint, _server.py:188): request properties from the input
        schema's columns, plus summary/description/tags/examples from the
        EndpointDocumentation."""
        _PRIMITIVES = {int: "integer", float: "number", bool: "boolean", str: "string"}
        properties = {}
        if schema is not None:
            for name, col in schema.__columns__.items():
                hint = getattr(col.dtype, "typehint", str)
                properties[name] = {
                    "type": _PRIMITIVES.get(hint, "string")
                }
        body_schema = {"type": "object", "properties": properties}
        item: dict = {}
        doc = documentation
        for m in methods:
            op: dict = {"responses": {"200": {"description": "OK"}}}
            if doc is not None:
                if doc.summary:
                    op["summary"] = doc.summary
                if doc.description:
                    op["description"] = doc.description
                if doc.tags:
                    op["tags"] = list(doc.tags)
            content: dict = {"schema": body_schema}
            if doc is not None and getattr(doc, "examples", None) is not None:
                content["examples"] = doc.examples._openapi_description()
            if m.upper() in ("POST", "PUT", "PATCH"):
                op["requestBody"] = {
                    "content": {"application/json": content}
                }
            item[m.lower()] = op
        return item

    def openapi_description_json(self) -> dict:
        return {
            "openapi": "3.0.3",
            "info": {"title": "Pathway REST API", "version": "1.0.0"},
            "paths": dict(self._route_docs),
        }

    def _start(self) -> None:
        if self._started:
            return
        self._started = True

        def serve():
            from aiohttp import web

            async def dispatch(request: "web.Request"):
                if (
                    self.with_schema_endpoint
                    and request.method == "GET"
                    and request.path == "/_schema"
                ):
                    return web.json_response(self.openapi_description_json())
                handler = self._routes.get((request.method, request.path))
                if handler is None:
                    return web.json_response({"error": "no such route"}, status=404)
                return await handler(request)

            async def main():
                try:
                    app = web.Application()
                    app.router.add_route("*", "/{tail:.*}", dispatch)
                    runner = web.AppRunner(app)
                    await runner.setup()
                    site = web.TCPSite(runner, self.host, self.port)
                    await site.start()
                except BaseException as exc:  # bind failure, bad host, …
                    self._startup_error = exc
                    self._ready.set()
                    return
                self._ready.set()
                while True:
                    await asyncio.sleep(3600)

            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(main())

        t = threading.Thread(target=serve, name="pathway:webserver", daemon=True)
        t.start()
        # a swallowed bind failure here used to surface as every request
        # timing out two minutes later — propagate loudly instead
        if not self._ready.wait(timeout=10):
            raise RuntimeError(
                f"webserver on {self.host}:{self.port} did not become "
                "ready within 10 s"
            )
        if self._startup_error is not None:
            raise RuntimeError(
                f"webserver failed to start on {self.host}:{self.port}: "
                f"{self._startup_error!r} (is the port already in use?)"
            ) from self._startup_error


class _RestSubject(Reader):
    """Bridges HTTP requests into the input table.

    Every request passes the process-global admission controller
    (``engine/serving.py``) before its row is emitted, carries a
    deadline (``X-Pathway-Deadline-Ms`` header, default
    ``PATHWAY_SERVE_DEADLINE_MS``) stamped onto the row, and is answered
    typed on every path — 400 malformed, 429 overloaded (+Retry-After),
    503 draining, 504 deadline, 500 pipeline error — never a stranded
    socket."""

    def __init__(self, webserver: PathwayWebserver, route: str, methods: list[str], schema, delete_completed_queries: bool, documentation=None, degraded_handler=None):
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.schema = schema
        self.delete_completed_queries = delete_completed_queries
        self.documentation = documentation
        self.degraded_handler = degraded_handler
        self.futures: dict[int, asyncio.Future] = {}
        self._seq = itertools.count()
        self._emit = None
        self._stop = threading.Event()

    def _count(self, code: int, route_label: str) -> None:
        get_registry().counter(
            "serve.requests", "REST requests answered, by status code",
            code=str(code), route=route_label,
        ).inc()

    def _reject(self, web, route_label: str, rej: serving.ServeRejected):
        self._count(rej.status, route_label)
        headers = {}
        if rej.retry_after_s:
            headers["Retry-After"] = str(int(rej.retry_after_s))
        return web.json_response(
            {"error": rej.message}, status=rej.status, headers=headers
        )

    def run(self, emit) -> None:
        self._emit = emit
        names = list(self.schema.__columns__.keys())
        dtypes = {n: self.schema.__columns__[n].dtype for n in names}
        route_label = safe_label(self.route)

        async def handler(request):
            from aiohttp import web

            if request.method in ("POST", "PUT", "PATCH"):
                body = await request.read()
                if body:
                    try:
                        payload = _json.loads(body)
                    except ValueError:
                        self._count(400, route_label)
                        return web.json_response(
                            {"error": "malformed JSON payload"}, status=400
                        )
                    if not isinstance(payload, dict):
                        self._count(400, route_label)
                        return web.json_response(
                            {"error": "JSON payload must be an object"},
                            status=400,
                        )
                else:
                    payload = {}
            else:
                body = b""
                payload = dict(request.query)
            header = request.headers.get(DEADLINE_HEADER)
            if header is not None:
                try:
                    deadline_ms = float(header)
                    if deadline_ms <= 0:
                        raise ValueError(header)
                except ValueError:
                    self._count(400, route_label)
                    return web.json_response(
                        {"error": f"invalid {DEADLINE_HEADER} header"},
                        status=400,
                    )
            else:
                deadline_ms = env_float("PATHWAY_SERVE_DEADLINE_MS")
            deadline = serving.Deadline.from_ms(deadline_ms)
            controller = serving.get_controller()
            serving.maybe_flood(self.route)  # chaos: request_flood
            tracing.maybe_trace_storm(self.route)  # chaos: trace_storm
            ingress_started = _time.time()
            try:
                ticket = await controller.admit(
                    self.route,
                    len(body),
                    deadline,
                    trace_parent=request.headers.get(TRACEPARENT_HEADER),
                )
            except serving.ServeRejected as rej:
                return self._reject(web, route_label, rej)
            trace = ticket.trace
            if trace is not None:
                trace.add_span(
                    "serve.ingress",
                    ingress_started,
                    max(0.0, _time.time() - ingress_started),
                    method=request.method,
                    nbytes=len(body),
                )
            started = _time.monotonic()
            code = 500
            try:
              with tracing.trace_scope(trace):
                # chaos: slow_handler stalls while HOLDING the admission
                # slot — queue delay climbs, shedding paths fire
                stall_s = serving.slow_handler_delay_s(self.route)
                if stall_s > 0.0:
                    await asyncio.sleep(stall_s)
                if controller.degraded and self.degraded_handler is not None:
                    value = self.degraded_handler(payload)
                    if asyncio.iscoroutine(value):
                        value = await value
                    code = 200
                    get_registry().counter(
                        "serve.degraded.served",
                        "requests answered by a degraded_handler",
                        route=route_label,
                    ).inc()
                    return web.json_response(
                        _jsonable(value), headers={"X-Pathway-Degraded": "1"}
                    )
                rid = next(self._seq)
                key = hash_values(["rest", id(self), rid])
                row = {"_pw_key": key, _utils.DEADLINE_TS: deadline.at}
                if trace is not None:
                    # the trace rides the row exactly like the deadline:
                    # downstream wait points (staging, batcher, device)
                    # attribute their spans to it without an ambient hop
                    row[tracing.TRACE_STAMP] = trace.traceparent()
                for n in names:
                    v = payload.get(n)
                    if dtypes[n].strip_optional() is dt.JSON and v is not None:
                        v = Json(v)
                    row[n] = v
                loop = asyncio.get_event_loop()
                future = loop.create_future()
                self.futures[key] = future
                serving.register_request(
                    key, lambda status, msg, _k=key: self.fail(_k, status, msg)
                )
                # key→trace binding: the async-UDF node re-enters this
                # trace's scope when it computes this row (the epoch-
                # thread hop of the trace)
                tracing.bind_key(key, trace)
                if trace is not None:
                    # where ``serve.epoch.wait`` starts; stamped before the
                    # row leaves, since the engine thread may stage it at once
                    trace.committed_at = _time.time()
                emit(row)
                emit(COMMIT)
                pipeline_started = _time.time()
                try:
                    result = await asyncio.wait_for(
                        future, timeout=max(0.0, deadline.remaining_s())
                    )
                except asyncio.TimeoutError:
                    code = 504
                    serving.note_deadline_shed("handler")
                    return web.json_response(
                        {"error": "deadline exceeded"}, status=504
                    )
                finally:
                    if trace is not None:
                        trace.add_span(
                            "serve.pipeline",
                            pipeline_started,
                            max(0.0, _time.time() - pipeline_started),
                        )
                    serving.unregister_request(key)
                    tracing.unbind_key(key)
                    self.futures.pop(key, None)
                    if self.delete_completed_queries:
                        drow = dict(row)
                        drow[_utils.DELETE] = True
                        emit(drow)
                        emit(COMMIT)
                if isinstance(result, serving.ServeRejected):
                    # typed completion from the pipeline side: row error,
                    # staging shed, or result retraction
                    code = result.status
                    return web.json_response(
                        {"error": result.message}, status=result.status
                    )
                code = 200
                return web.json_response(result)
            finally:
                latency_ms = (_time.monotonic() - started) * 1000.0
                self._count(code, route_label)
                if code == 200:
                    get_registry().histogram(
                        "serve.latency.ms",
                        "admitted-request end-to-end latency (ms)",
                        buckets=MS_BUCKETS,
                        route=route_label,
                    ).observe(
                        latency_ms,
                        trace_id=trace.trace_id if trace is not None else None,
                    )
                if trace is not None:
                    trace.finish(status=code)
                controller.release(ticket, code=code, latency_ms=latency_ms)

        self.webserver._add_route(
            self.route,
            self.methods,
            handler,
            schema=self.schema,
            documentation=self.documentation,
        )
        self.webserver._start()
        self._stop.wait()  # run forever (streaming source)

    def complete(self, key: int, value: Any) -> None:
        future = self.futures.get(key)
        if future is not None and not future.done():
            loop = future.get_loop()
            loop.call_soon_threadsafe(
                lambda: future.done() or future.set_result(value)
            )

    def fail(self, key: int, status: int, message: str) -> None:
        """Complete a waiting request with a typed error (pipeline row
        error, staging shed, or result retraction) — threadsafe, no-op
        once the future resolved or the request finished."""
        future = self.futures.get(key)
        if future is None:
            return
        if status == 504:
            err: serving.ServeRejected = serving.DeadlineExceededError(message)
        else:
            err = serving.RequestFailedError(message)
        loop = future.get_loop()
        loop.call_soon_threadsafe(
            lambda: future.done() or future.set_result(err)
        )


def _jsonable(v):
    if isinstance(v, Json):
        return v.value
    if isinstance(v, Pointer):
        return repr(v)
    if isinstance(v, bytes):
        return v.decode("utf-8", errors="replace")
    if isinstance(v, tuple):
        return [_jsonable(x) for x in v]
    try:
        import numpy as np

        if isinstance(v, np.ndarray):
            return v.tolist()
        if isinstance(v, np.generic):
            return v.item()
    except ImportError:
        pass
    return v


def rest_connector(
    host: str | None = None,
    port: int | None = None,
    *,
    webserver: PathwayWebserver | None = None,
    route: str = "/",
    methods: list[str] = ("POST",),
    schema: type[schema_mod.Schema] | None = None,
    autocommit_duration_ms: int | None = 50,
    keep_queries: bool | None = None,
    delete_completed_queries: bool = False,
    request_validator=None,
    documentation: EndpointDocumentation | None = None,
    degraded_handler=None,
) -> tuple[Table, Any]:
    """Returns (queries_table, response_writer).

    ``degraded_handler`` — optional plain callable (or coroutine
    function) ``payload_dict -> jsonable``: while the load shedder is
    engaged (``serve.degraded`` gauge), requests to this route are
    answered by it directly (``X-Pathway-Degraded: 1`` response header)
    instead of entering the pipeline — e.g. retrieval without the rerank
    stage.  See docs/serving.md."""
    if webserver is None:
        if host is None or port is None:
            raise ValueError("provide webserver= or host=/port=")
        webserver = PathwayWebserver(host, port)
    if schema is None:
        schema = schema_mod.schema_from_types(query=str)
    subject = _RestSubject(
        webserver, route, list(methods), schema, delete_completed_queries,
        documentation=documentation, degraded_handler=degraded_handler,
    )
    table = _utils.make_input_table(
        schema,
        lambda: subject,
        autocommit_duration_ms=autocommit_duration_ms,
    )

    def response_writer(response_table: Table) -> None:
        names = response_table.column_names()

        def on_data(key, row, time, diff):
            if diff <= 0:
                # the pipeline retracted the result row while the client
                # is still waiting (delete_completed_queries retractions
                # arrive AFTER completion and no-op here): typed 500
                # instead of a silent 504 two minutes later
                subject.fail(key, 500, "result row retracted by the pipeline")
                return
            from pathway_tpu.engine.types import Error as _Error

            if any(isinstance(v, _Error) for v in row):
                # a poisoned cell (division by zero, bad cast) reached the
                # response: typed 500, never a JSON-serialization crash
                subject.fail(
                    key, 500, "result row contains an error value"
                )
                return
            if "result" in names:
                value = _jsonable(row[names.index("result")])
            else:
                value = {n: _jsonable(v) for n, v in zip(names, row)}
            subject.complete(key, value)

        _utils.register_output(response_table, on_data, name=f"rest:{route}")

    return table, response_writer
