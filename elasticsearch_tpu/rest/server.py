"""HTTP server: stdlib threading server fronting RestActions.

Reference analog: org.elasticsearch.http.AbstractHttpServerTransport +
modules/transport-netty4 Netty4HttpServerTransport — here a
ThreadingHTTPServer (one thread per connection, the 'http_server_worker'
pool analog) because the compute path is device-bound, not socket-bound.
NDJSON endpoints (_bulk, _msearch) are split/parsed here, mirroring
RestBulkAction's line-by-line XContent parsing.

Run: ``python -m elasticsearch_tpu.rest.server --port 9200 [--data-path d]``
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, unquote, urlparse

from jax.profiler import TraceAnnotation

from ..cluster import ClusterError, ClusterService
from ..common.memory import CircuitBreakingException
from ..common.tracing import OPAQUE_ID_CTX, REQUEST_CTX, RequestMarks
from ..index.engine import EngineError, VersionConflictError
from ..index.mapping import MappingParseError
from ..search.admission import EsOverloadedError, admission, overload_body
from ..search.aggs import AggParseError
from ..search.batcher import EsRejectedExecutionError
from ..search.dsl import QueryParseError
from ..tasks import TaskCancelledException
from .actions import RestActions
from .router import error_body

NDJSON_PATHS = frozenset({"_bulk", "_msearch"})


class ElasticHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "elasticsearch-tpu"
    actions: RestActions  # set on the server class

    # silence per-request stderr logging
    def log_message(self, fmt, *args):
        pass

    def setup(self):
        super().setup()
        # this connection thread's marks of the request it is serving
        # (common/tracing.py: the `http` span and its children)
        self.marks = RequestMarks()
        self._on_profiler: Optional[TraceAnnotation] = None

    def handle_one_request(self):
        """One request, then its trace: a search action leaves the trace
        it armed with `self.marks`, and it is closed here, after the
        response's last byte, whatever way the request ended."""
        try:
            super().handle_one_request()
        finally:
            ann, self._on_profiler = self._on_profiler, None
            if ann is not None:
                ann.__exit__(None, None, None)
                self.marks.finish(self.command)

    def parse_request(self):
        # the request line has been read: the wait in front of it (an
        # idle keep-alive connection) was the client's, not the
        # request's. From here to the response's last byte the thread is
        # inside `es.http` on the profiler's clock (outside a profiler
        # session a flag test)
        self.marks.t_line = time.perf_counter_ns()
        self._on_profiler = TraceAnnotation("es.http")
        self._on_profiler.__enter__()
        return super().parse_request()

    def _read_body(self) -> bytes:
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length) if length else b""

    def _respond(
        self, status: int, payload, head_only: bool = False,
        headers: Optional[dict] = None,
    ) -> None:
        m = self.marks
        m.status = status
        m.t_respond = time.perf_counter_ns()
        try:
            if isinstance(payload, bytes):  # a JSON document, encoded
                data, ctype = payload, "application/json"
            elif isinstance(payload, (dict, list)):
                data = json.dumps(payload).encode()
                ctype = "application/json"
            else:
                data = str(payload).encode()
                ctype = "text/plain; charset=UTF-8"
            m.t_dumped = time.perf_counter_ns()
            m.response_bytes = len(data)
            self.send_response(status)
            self.send_header("X-elastic-product", "Elasticsearch")
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            for k, v in (headers or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            if not head_only:
                self.wfile.write(data)
        finally:
            m.t_end = time.perf_counter_ns()

    def _handle(self, method: str) -> None:
        m = self.marks
        raw = self._read_body()
        m.request_bytes = len(raw)
        m.t_read = time.perf_counter_ns()
        parsed = urlparse(self.path)
        path = parsed.path
        qs = parse_qs(parsed.query, keep_blank_values=True)
        head_only = method == "HEAD"
        route, params, path_exists = self.actions.router.dispatch(method, path)
        # percent-decode extracted path params AFTER routing so an
        # encoded %2F stays inside one path segment during dispatch but
        # the handler sees the client's literal id ("a%20b" → "a b") —
        # RestUtils.decodeComponent semantics
        if params:
            params = {k: unquote(v) for k, v in params.items()}
        if route is None:
            if path_exists:
                self._respond(
                    405,
                    error_body(
                        405,
                        "method_not_allowed_exception",
                        f"Incorrect HTTP method for uri [{self.path}] and "
                        f"method [{method}]",
                    ),
                    head_only,
                )
            else:
                self._respond(
                    400,
                    error_body(
                        400,
                        "illegal_argument_exception",
                        f"no handler found for uri [{path}] and method [{method}]",
                    ),
                    head_only,
                )
            return
        resp_headers: Optional[dict] = None
        # X-Opaque-Id rides a contextvar for the request's lifetime so
        # task descriptions, traces, and slow logs can stamp it
        opaque_tok = OPAQUE_ID_CTX.set(self.headers.get("X-Opaque-Id"))
        request_tok = REQUEST_CTX.set(m)
        try:
            body = self._parse_body(path, raw)
            m.t_parsed = time.perf_counter_ns()
            status, payload = route.handler(body, params or {}, qs)
        except ClusterError as e:
            status, payload = e.status, error_body(e.status, e.err_type, e.reason)
        except VersionConflictError as e:
            status, payload = 409, error_body(
                409, "version_conflict_engine_exception", str(e)
            )
        except (QueryParseError, MappingParseError, AggParseError) as e:
            status, payload = 400, error_body(400, "parsing_exception", str(e))
        except (
            EsOverloadedError, EsRejectedExecutionError,
            CircuitBreakingException,
        ) as e:
            # EVERY overload rejection — admission shed, bounded-queue
            # overflow (EsRejectedExecutionException contract), HBM
            # breaker — is a 429 with a computed Retry-After header and
            # the structured es.overloaded body block
            retry_after = getattr(e, "retry_after", None)
            if retry_after is None:
                retry_after = admission.retry_after_s()
            status, payload = 429, overload_body(e, retry_after)
            resp_headers = {"Retry-After": int(retry_after)}
        except TaskCancelledException as e:
            # a cancelled search surfaces as 400 task_cancelled_exception
            # (TransportSearchAction's cancellation contract)
            status, payload = 400, error_body(
                400, "task_cancelled_exception", str(e)
            )
        except EngineError as e:
            status, payload = 500, error_body(500, "engine_exception", str(e))
        except json.JSONDecodeError as e:
            status, payload = 400, error_body(
                400, "json_parse_exception", f"invalid JSON: {e}"
            )
        except Exception as e:  # the 500 of last resort
            status, payload = 500, error_body(500, "exception", repr(e))
        finally:
            REQUEST_CTX.reset(request_tok)
            OPAQUE_ID_CTX.reset(opaque_tok)
        self._respond(status, payload, head_only, headers=resp_headers)

    def _parse_body(self, path: str, raw: bytes):
        last = path.rstrip("/").rsplit("/", 1)[-1]
        if not raw:
            return [] if last in NDJSON_PATHS else None
        text = raw.decode("utf-8")
        if last == "_bulk":
            return [json.loads(l) for l in text.splitlines() if l.strip()]
        if last == "_msearch":
            lines = [json.loads(l) for l in text.splitlines() if l.strip()]
            pairs = []
            i = 0
            while i < len(lines):
                header = lines[i]
                if i + 1 < len(lines):
                    pairs.append((header, lines[i + 1]))
                    i += 2
                else:
                    pairs.append((header, {}))
                    i += 1
            return pairs
        return json.loads(text)

    def do_GET(self):
        self._handle("GET")

    def do_POST(self):
        self._handle("POST")

    def do_PUT(self):
        self._handle("PUT")

    def do_DELETE(self):
        self._handle("DELETE")

    def do_HEAD(self):
        # index/doc existence checks: HEAD maps onto the GET handler
        self._handle("HEAD")


class ElasticsearchTpuServer:
    """Owns the ClusterService + HTTP listener (Node.start analog)."""

    def __init__(
        self,
        port: int = 9200,
        host: str = "127.0.0.1",
        data_path: Optional[str] = None,
        cluster: Optional[ClusterService] = None,
    ):
        self.cluster = cluster or ClusterService(data_path=data_path)
        self.actions = RestActions(self.cluster)
        handler = type("BoundHandler", (ElasticHandler,), {"actions": self.actions})
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start_background(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.cluster.close()


def main(argv=None):
    # plugins install BEFORE any registry is consumed (NodeConstruction
    # ordering): ES_TPU_PLUGINS="module.path:ClassName,..."
    from ..common.compile_cache import configure_compile_cache
    from ..plugins import plugins_service

    plugins_service.load_env()
    # before JAX compiles anything: a cold node otherwise recompiles
    # every kernel family x every row bucket on each start
    configure_compile_cache()
    ap = argparse.ArgumentParser(description="elasticsearch-tpu node")
    ap.add_argument("--port", type=int, default=9200, help="HTTP port")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--data-path", default=None)
    ap.add_argument(
        "--node-name", default=None, help="start a cluster node (transport on)"
    )
    ap.add_argument(
        "--transport-port", type=int, default=9300, help="inter-node RPC port"
    )
    ap.add_argument(
        "--seeds",
        default=None,
        help="comma-separated host:port seed list (discovery.seed_hosts)",
    )
    args = ap.parse_args(argv)
    node = None
    if args.node_name is not None or args.seeds is not None:
        # multi-node mode: the HTTP tier fronts a TpuNode's distributed
        # cluster service (Netty4HttpServerTransport + TransportService
        # both bound on one Node, SURVEY.md §3.1)
        from ..cluster.node import TpuNode

        seeds = []
        for part in (args.seeds or "").split(","):
            part = part.strip()
            if part:
                h, _, p = part.rpartition(":")
                seeds.append((h or "127.0.0.1", int(p)))
        node = TpuNode(
            args.node_name or "node-0",
            seeds=seeds,
            data_path=args.data_path,
            port=args.transport_port,
        ).start()
        server = ElasticsearchTpuServer(
            port=args.port, host=args.host, cluster=node.cluster
        )
        print(
            f"elasticsearch-tpu node [{node.name}] transport "
            f"{node.address[0]}:{node.address[1]} http://{args.host}:{server.port}"
            f" (data: {args.data_path or 'in-memory'})",
            flush=True,
        )
    else:
        server = ElasticsearchTpuServer(
            port=args.port, host=args.host, data_path=args.data_path
        )
        print(
            f"elasticsearch-tpu listening on http://{args.host}:{server.port} "
            f"(data: {args.data_path or 'in-memory'})",
            flush=True,
        )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.close()
        if node is not None:
            node.close()


if __name__ == "__main__":
    main()
