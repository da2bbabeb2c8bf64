"""RPC core: msgpack-over-gRPC with typed error propagation.

Re-design of the reference's transport layer (``core/common/.../grpc/
{GrpcServerBuilder,GrpcChannelBuilder,GrpcConnectionPool.java:46}`` + 26
generated proto services). Design departure, on purpose: instead of protoc
codegen we register **generic gRPC handlers** keyed by method name with
msgpack message bodies — same HTTP/2 transport, flow control and streaming
semantics as the reference, zero generated code, and messages are the same
dicts the wire types already serialize to. The reference's zero-copy
marshalling trick (``GrpcSerializationUtils.java:39``) is unnecessary here:
bulk data rides raw ``bytes`` fields in msgpack (no protobuf copy), and the
truly hot local path bypasses RPC entirely via shm short-circuit.

Errors: handlers raising ``AlluxioTpuError`` are mapped onto gRPC status +
a serialized typed payload in trailing metadata; clients re-raise the exact
exception class (reference: ``exception/status`` <-> ``io.grpc.Status``).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent import futures
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import grpc
import msgpack

from alluxio_tpu.metrics import metrics
from alluxio_tpu.utils.exceptions import (
    AlluxioTpuError, ResourceExhaustedError, UnavailableError,
)
from alluxio_tpu.utils.tracing import (
    TRACEPARENT_KEY, bind_remote_parent, current_span,
    current_traceparent, reset_remote_parent, tracer,
)

LOG = logging.getLogger(__name__)

_ERROR_KEY = "atpu-error-bin"


def _bind_trace(context: grpc.ServicerContext):
    """Extract an inbound traceparent and bind it as this handler's
    parent context, so the server span joins the caller's trace.
    Returns a reset token (None when tracing is off / no header)."""
    if not tracer().enabled:
        return None
    for k, v in (context.invocation_metadata() or ()):
        if k == TRACEPARENT_KEY:
            return bind_remote_parent(v)
    return None

_CODE_TO_GRPC = {
    "NOT_FOUND": grpc.StatusCode.NOT_FOUND,
    "ALREADY_EXISTS": grpc.StatusCode.ALREADY_EXISTS,
    "INVALID_ARGUMENT": grpc.StatusCode.INVALID_ARGUMENT,
    "PERMISSION_DENIED": grpc.StatusCode.PERMISSION_DENIED,
    "UNAUTHENTICATED": grpc.StatusCode.UNAUTHENTICATED,
    "FAILED_PRECONDITION": grpc.StatusCode.FAILED_PRECONDITION,
    "RESOURCE_EXHAUSTED": grpc.StatusCode.RESOURCE_EXHAUSTED,
    "UNAVAILABLE": grpc.StatusCode.UNAVAILABLE,
    "DEADLINE_EXCEEDED": grpc.StatusCode.DEADLINE_EXCEEDED,
    "CANCELLED": grpc.StatusCode.CANCELLED,
    "ABORTED": grpc.StatusCode.ABORTED,
    "UNIMPLEMENTED": grpc.StatusCode.UNIMPLEMENTED,
    "INTERNAL": grpc.StatusCode.INTERNAL,
}


def pack(obj: Any) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def unpack(data: bytes) -> Any:
    return msgpack.unpackb(data, raw=False, strict_map_key=False)


def _bind_user(context: grpc.ServicerContext, authenticator):
    """Authenticate request metadata and bind the user contextvar; returns
    a reset token (or None). Raises AlluxioTpuError on rejection."""
    if authenticator is None:
        return None
    from alluxio_tpu.security.user import set_authenticated_user

    md = {k: v for k, v in (context.invocation_metadata() or ())}
    user = authenticator.authenticate(md)
    return set_authenticated_user(user)


def _unbind_user(token) -> None:
    if token is not None:
        from alluxio_tpu.security.user import reset_authenticated_user

        reset_authenticated_user(token)


def check_admission(admission, context, method_key: str,
                    principal_hint: Optional[str] = None) -> None:
    """Per-dispatch QoS gate, shared by the gRPC wrappers and the
    fastpath server: the conf-gated fault hook first (so shedding can
    be chaos-drilled with no admission controller and no flood), then
    the per-principal token bucket.  Raises a typed
    ``ResourceExhaustedError`` carrying ``retry_after_s`` — the RPC is
    SHED, never queued (see qos/admission.py).  ``principal_hint``:
    transport-specific identity fallback for servers without a gRPC
    context (the fastpath passes its hello-frame ``atpu-user``)."""
    from alluxio_tpu.utils import faults

    if faults.armed():
        # the chaos drill honors the same exemptions real admission
        # does — shedding registration/heartbeats would destabilize
        # the cluster the drill is observing
        from alluxio_tpu.qos.admission import DEFAULT_EXEMPT

        exempt = admission.conf.exempt if admission is not None \
            else DEFAULT_EXEMPT
        if method_key.rsplit(".", 1)[-1] not in exempt:
            ra = faults.injector().take_rpc_reject(method_key)
            if ra:
                err = ResourceExhaustedError(
                    f"injected rpc reject for {method_key}; retry "
                    f"after {ra:.3f}s")
                err.retry_after_s = ra
                raise err
    if admission is None:
        return
    principal = principal_hint
    from alluxio_tpu.security.user import authenticated_user

    user = authenticated_user()
    if user is not None:
        principal = user.name
    elif principal is None and context is not None:
        # NOSASL server: fall back to the identity metadata clients
        # attach anyway, so admission can still separate principals
        for k, v in (context.invocation_metadata() or ()):
            if k == "atpu-user":
                principal = v
                break
    admission.check(principal, method_key.rsplit(".", 1)[-1])


def _timed_admission(sp, admission, context, span_name: str,
                     principal_hint: Optional[str] = None) -> None:
    """check_admission, recording its cost as the server span's
    ``admission`` phase when the dispatch is traced (both unary
    dispatchers: the gRPC wrappers and ``rpc/fastpath.py``)."""
    if sp is None:
        check_admission(admission, context, span_name, principal_hint)
        return
    import time as _time

    t0 = _time.perf_counter()
    check_admission(admission, context, span_name, principal_hint)
    sp.phase("admission", (_time.perf_counter() - t0) * 1000.0)


#: the two unary dispatchers, as ``RpcServed`` names them
ROUTE_GRPC, ROUTE_FASTPATH = "grpc", "fastpath"


def _served_metrics(reg, service: str, method: str, route: str):
    """``(timer, counter)`` of one method on one route. The instance is
    the serving role, read off the service's name (an in-process cluster
    shares one registry, so the registry's own instance cannot say)."""
    if service.endswith("Worker"):
        return (reg.timer(f"Worker.RpcServeTime.{method}"),
                reg.counter(f"Worker.RpcServed.{route}.{method}"))
    if service.startswith("Job"):
        return (reg.timer(f"JobMaster.RpcServeTime.{method}"),
                reg.counter(f"JobMaster.RpcServed.{route}.{method}"))
    return (reg.timer(f"Master.RpcServeTime.{method}"),
            reg.counter(f"Master.RpcServed.{route}.{method}"))


def register_served(service: str, methods) -> None:
    """Make these methods' ``RpcServed`` counters exist on BOTH routes
    (and their serve timer, empty), so that a route never taken reads 0
    in a ``get_metrics`` pull and not "absent"."""
    reg = metrics()
    for route in (ROUTE_GRPC, ROUTE_FASTPATH):
        for method in methods:
            _served_metrics(reg, service, method, route)


class ServeTimer:
    """The always-on per-method server timer, ring on or off (the
    reference's ``RpcUtils`` per-method timer): handler wall time as the
    SERVER saw it, so a client-side figure splits into stub + wire
    against service time, and beside it the count of what THIS route
    served, ``<Instance>.RpcServed.<route>.<method>`` (the routes share
    the timer; the counter says which one a client took). One a method
    and route, built when the method is registered by either unary
    dispatcher (the gRPC wrapper below, ``rpc/fastpath.py``), so an RPC
    pays one ``Timer.update`` and one ``Counter.inc``. Both are looked
    up again only when the process registry was swapped
    (``reset_metrics``, in tests)."""

    __slots__ = ("_service", "_method", "_route", "_reg", "_timer",
                 "_served")

    def __init__(self, service: str, method: str, route: str) -> None:
        self._service, self._method, self._route = service, method, route
        self._reg = self._timer = self._served = None

    def update(self, seconds: float) -> None:
        reg = metrics()
        if reg is not self._reg:
            self._timer, self._served = _served_metrics(
                reg, self._service, self._method, self._route)
            self._reg = reg
        self._timer.update(seconds)
        self._served.inc()


def _wrap_unary(fn: Callable[[dict], Any], authenticator=None,
                span_name: str = "", admission=None) -> Callable:
    service, _, method = span_name.rpartition(".")
    serve_timer = ServeTimer(service, method, ROUTE_GRPC) \
        if method else None

    def handler(request: dict, context: grpc.ServicerContext):
        token = None
        trace_token = _bind_trace(context)
        t0 = time.perf_counter()
        try:
            with tracer().span(span_name or "rpc.unary") as sp:
                token = _bind_user(context, authenticator)
                _timed_admission(sp, admission, context, span_name)
                return fn(request or {})
        except AlluxioTpuError as e:
            context.set_trailing_metadata(((_ERROR_KEY, pack(e.to_wire())),))
            context.abort(_CODE_TO_GRPC.get(e.code, grpc.StatusCode.INTERNAL),
                          str(e))
        except Exception as e:  # noqa: BLE001
            LOG.exception("unhandled error in RPC handler")
            context.abort(grpc.StatusCode.INTERNAL, f"{type(e).__name__}: {e}")
        finally:
            if serve_timer is not None:
                serve_timer.update(time.perf_counter() - t0)
            _unbind_user(token)
            reset_remote_parent(trace_token)

    return handler


def _wrap_stream_out(fn: Callable[[dict], Iterator[Any]],
                     authenticator=None, span_name: str = "",
                     admission=None) -> Callable:
    def handler(request: dict, context: grpc.ServicerContext):
        token = None
        trace_token = _bind_trace(context)
        try:
            with tracer().span(span_name or "rpc.stream_out") as sp:
                token = _bind_user(context, authenticator)
                _timed_admission(sp, admission, context, span_name)
                yield from fn(request or {})
        except AlluxioTpuError as e:
            context.set_trailing_metadata(((_ERROR_KEY, pack(e.to_wire())),))
            context.abort(_CODE_TO_GRPC.get(e.code, grpc.StatusCode.INTERNAL),
                          str(e))
        except Exception as e:  # noqa: BLE001
            LOG.exception("unhandled error in streaming RPC handler")
            context.abort(grpc.StatusCode.INTERNAL, f"{type(e).__name__}: {e}")
        finally:
            _unbind_user(token)
            reset_remote_parent(trace_token)

    return handler


def _wrap_stream_in(fn: Callable[[Iterator[Any]], Any],
                    authenticator=None, span_name: str = "",
                    admission=None) -> Callable:
    def handler(request_iterator, context: grpc.ServicerContext):
        token = None
        trace_token = _bind_trace(context)
        try:
            with tracer().span(span_name or "rpc.stream_in") as sp:
                token = _bind_user(context, authenticator)
                _timed_admission(sp, admission, context, span_name)
                return fn(request_iterator)
        except AlluxioTpuError as e:
            context.set_trailing_metadata(((_ERROR_KEY, pack(e.to_wire())),))
            context.abort(_CODE_TO_GRPC.get(e.code, grpc.StatusCode.INTERNAL),
                          str(e))
        except Exception as e:  # noqa: BLE001
            LOG.exception("unhandled error in client-streaming RPC handler")
            context.abort(grpc.StatusCode.INTERNAL, f"{type(e).__name__}: {e}")
        finally:
            _unbind_user(token)
            reset_remote_parent(trace_token)

    return handler


class ServiceDefinition:
    """A named service: method name -> (callable, kind)."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.methods: Dict[str, Tuple[Callable, str]] = {}

    def unary(self, method: str, fn: Callable[[dict], Any]) -> None:
        self.methods[method] = (fn, "unary")

    def stream_out(self, method: str, fn: Callable[[dict], Iterator[Any]]) -> None:
        self.methods[method] = (fn, "stream_out")

    def stream_in(self, method: str, fn: Callable[[Iterator[Any]], Any]) -> None:
        self.methods[method] = (fn, "stream_in")


class _GenericHandler(grpc.GenericRpcHandler):
    def __init__(self, services: Dict[str, ServiceDefinition],
                 authenticator=None, admission=None) -> None:
        self._services = services
        self._auth = authenticator
        self._admission = admission
        #: (service, method) -> (fn, handler): grpc asks for the handler
        #: on EVERY call, the wrapper (and its serve timer) is built
        #: once a method
        self._unary: Dict[Tuple[str, str], Tuple[Callable, Any]] = {}

    def service(self, handler_call_details):
        # method path: /<service>/<method>
        _, _, rest = handler_call_details.method.partition("/")
        service_name, _, method = rest.partition("/")
        svc = self._services.get(service_name)
        if svc is None:
            return None
        entry = svc.methods.get(method)
        if entry is None:
            return None
        fn, kind = entry
        span = f"{service_name}.{method}"
        if kind == "unary":
            cached = self._unary.get((service_name, method))
            # a handler wrapped in place after start() (the HA primacy
            # fence) is a new ``fn``: wrap that one afresh
            if cached is None or cached[0] is not fn:
                cached = (fn, grpc.unary_unary_rpc_method_handler(
                    _wrap_unary(fn, self._auth, span, self._admission),
                    request_deserializer=unpack,
                    response_serializer=pack))
                self._unary[(service_name, method)] = cached
            return cached[1]
        if kind == "stream_out":
            return grpc.unary_stream_rpc_method_handler(
                _wrap_stream_out(fn, self._auth, span, self._admission),
                request_deserializer=unpack, response_serializer=pack)
        if kind == "stream_in":
            return grpc.stream_unary_rpc_method_handler(
                _wrap_stream_in(fn, self._auth, span, self._admission),
                request_deserializer=unpack, response_serializer=pack)
        return None


class RpcServer:
    """gRPC server hosting ServiceDefinitions
    (reference: ``GrpcServerBuilder`` + ``GrpcDataServer.java:50``)."""

    def __init__(self, bind_host: str = "0.0.0.0", port: int = 0,
                 max_workers: int = 16,
                 domain_socket_path: Optional[str] = None,
                 authenticator=None, admission=None) -> None:
        """``authenticator``: a ``security.authentication.Authenticator``;
        when set, every RPC is authenticated and the resolved user is bound
        for handlers to read via ``security.authenticated_user()``.
        ``admission``: a ``qos.admission.AdmissionController``; when set,
        every dispatch passes its per-principal token bucket and
        over-limit calls are shed with a typed retry-after."""
        self._services: Dict[str, ServiceDefinition] = {}
        self._authenticator = authenticator
        self._admission = admission
        options = [
            ("grpc.max_send_message_length", 64 << 20),
            ("grpc.max_receive_message_length", 64 << 20),
            ("grpc.so_reuseport", 0),
        ]
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=options)
        self._bind = f"{bind_host}:{port}"
        self.port = port
        self._domain_socket_path = domain_socket_path
        self._started = False

    def add_service(self, svc: ServiceDefinition) -> None:
        self._services[svc.name] = svc

    def services(self) -> Tuple[ServiceDefinition, ...]:
        """Everything this server hosts: what a fast path beside it
        serves the unary half of (``fastpath.serve_fastpath``)."""
        return tuple(self._services.values())

    def service(self, name: str) -> Optional[ServiceDefinition]:
        """Registered service by name — dispatch reads the definition's
        method map per call, so callers may wrap handlers in place even
        after ``start()`` (the HA primacy fence does)."""
        return self._services.get(name)

    def start(self) -> int:
        self._server.add_generic_rpc_handlers(
            (_GenericHandler(self._services, self._authenticator,
                             self._admission),))
        self.port = self._server.add_insecure_port(self._bind)
        if self._domain_socket_path:
            # UDS endpoint for same-host traffic without TCP
            # (reference: GrpcDataServer.java:72-95 Netty domain sockets)
            self._server.add_insecure_port(
                f"unix://{self._domain_socket_path}")
        self._server.start()
        self._started = True
        return self.port

    def stop(self, grace_s: float = 0.5) -> None:
        if self._started:
            self._server.stop(grace_s).wait(timeout=5)


def _raise_typed(err: grpc.RpcError) -> None:
    md = dict(err.trailing_metadata() or ())
    blob = md.get(_ERROR_KEY)
    if blob is not None:
        raise AlluxioTpuError.from_wire(unpack(blob)) from None
    if err.code() == grpc.StatusCode.UNAVAILABLE:
        raise UnavailableError(err.details() or "server unavailable") from None
    raise AlluxioTpuError(
        f"{err.code().name}: {err.details()}") from None


def default_client_metadata() -> Tuple[Tuple[str, str], ...]:
    """Identity attached to calls when the caller supplies none: the OS
    user under SIMPLE auth (reference: LoginUser)."""
    from alluxio_tpu.security.user import get_os_user

    return (("atpu-user", get_os_user()),)


class StreamCall:
    """A cancellable server-stream: iterate for decoded messages, call
    :meth:`cancel` to abort the underlying HTTP/2 stream mid-flight
    (hedged reads cancel the losing transfer instead of draining it).
    A self-cancelled stream ends iteration quietly; every other gRPC
    error is re-raised typed like the plain ``call_stream`` path.

    When the stream was opened under a live span, per-chunk msgpack
    decode time accumulates in ``decode_cell`` and lands on that span
    as ONE ``serialize`` phase when iteration ends (per-chunk phase
    events would bloat a large read's span)."""

    __slots__ = ("_call", "cancelled", "_span", "_decode_cell")

    def __init__(self, call, span=None, decode_cell=None) -> None:
        self._call = call
        self.cancelled = False
        self._span = span
        self._decode_cell = decode_cell

    def cancel(self) -> None:
        self.cancelled = True
        self._call.cancel()

    def __iter__(self) -> Iterator[Any]:
        try:
            yield from self._call
        except grpc.RpcError as e:
            if self.cancelled and e.code() == grpc.StatusCode.CANCELLED:
                return
            _raise_typed(e)
        finally:
            if self._span is not None and self._decode_cell is not None \
                    and self._decode_cell[0] > 0.0:
                self._span.phase("serialize", self._decode_cell[0])
                self._decode_cell[0] = 0.0


class RpcChannel:
    """A pooled channel + method invokers (reference: GrpcConnectionPool
    multiplexes channels per NetworkGroup; grpc-python already multiplexes
    streams on one HTTP/2 connection, so one channel per address suffices
    — except for the parallel data plane, where ``pool_index`` > 0 mints
    additional channels with their own subchannel pool, i.e. their own
    TCP connections, so striped reads are not serialized behind one
    connection's flow-control window).
    ``metadata``: identity/credential tuples attached to every call
    (reference: the SASL-authenticated channel carrying the user)."""

    _pool: Dict[str, grpc.Channel] = {}
    _pool_lock = threading.Lock()

    def __init__(self, address: str,
                 metadata: Optional[Tuple[Tuple[str, str], ...]] = None,
                 pool_index: int = 0) -> None:
        self.address = address
        self.metadata = tuple(metadata) if metadata is not None \
            else default_client_metadata()
        key = address if pool_index == 0 else f"{address}#{pool_index}"
        with RpcChannel._pool_lock:
            ch = RpcChannel._pool.get(key)
            if ch is None:
                options = [
                    ("grpc.max_send_message_length", 64 << 20),
                    ("grpc.max_receive_message_length", 64 << 20),
                ]
                if pool_index:
                    # opt out of gRPC's global subchannel sharing:
                    # identical-args channels would otherwise coalesce
                    # onto the same TCP connection, defeating the pool
                    options.append(("grpc.use_local_subchannel_pool", 1))
                ch = grpc.insecure_channel(address, options=options)
                RpcChannel._pool[key] = ch
            self._channel = ch

    def _call_metadata(self) -> Tuple[Tuple[str, str], ...]:
        """Per-call metadata: the channel identity plus the caller's
        trace context, so the server span joins the caller's trace."""
        tp = current_traceparent()
        if tp is None:
            return self.metadata
        return self.metadata + ((TRACEPARENT_KEY, tp),)

    def call(self, service: str, method: str, request: dict,
             timeout: Optional[float] = 30.0) -> Any:
        fn = self._channel.unary_unary(
            f"/{service}/{method}", request_serializer=pack,
            response_deserializer=unpack)
        try:
            return fn(request, timeout=timeout,
                      metadata=self._call_metadata())
        except grpc.RpcError as e:
            _raise_typed(e)

    def call_stream(self, service: str, method: str, request: dict,
                    timeout: Optional[float] = 300.0) -> Iterator[Any]:
        fn = self._channel.unary_stream(
            f"/{service}/{method}", request_serializer=pack,
            response_deserializer=unpack)
        try:
            yield from fn(request, timeout=timeout,
                          metadata=self._call_metadata())
        except grpc.RpcError as e:
            _raise_typed(e)

    def open_stream(self, service: str, method: str, request: dict,
                    timeout: Optional[float] = 300.0) -> StreamCall:
        """Like :meth:`call_stream` but returns the live call wrapped as
        a :class:`StreamCall`, so the caller can ``cancel()`` it — the
        parallel read path races stripe transfers and must be able to
        abort the losers without draining them.

        Under a live span the request pack and the per-chunk decodes
        are timed into the span's ``serialize`` phase: the pack happens
        eagerly here (grpc gets the pre-packed blob via an identity
        serializer, so nothing is encoded twice) and decode time is
        accumulated by the deserializer closure until the stream ends."""
        sp = current_span()
        if sp is None:
            fn = self._channel.unary_stream(
                f"/{service}/{method}", request_serializer=pack,
                response_deserializer=unpack)
            return StreamCall(fn(request, timeout=timeout,
                                 metadata=self._call_metadata()))
        import time as _time

        clock = _time.perf_counter
        t0 = clock()
        blob = pack(request)
        sp.phase("serialize", (clock() - t0) * 1000.0)
        cell = [0.0]

        def _timed_unpack(data: bytes):
            t = clock()
            obj = unpack(data)
            cell[0] += (clock() - t) * 1000.0
            return obj

        fn = self._channel.unary_stream(
            f"/{service}/{method}",
            request_serializer=lambda _r: blob,
            response_deserializer=_timed_unpack)
        return StreamCall(fn(request, timeout=timeout,
                             metadata=self._call_metadata()),
                          span=sp, decode_cell=cell)

    def call_stream_in(self, service: str, method: str,
                       requests: Iterator[dict],
                       timeout: Optional[float] = 300.0) -> Any:
        fn = self._channel.stream_unary(
            f"/{service}/{method}", request_serializer=pack,
            response_deserializer=unpack)
        try:
            return fn(requests, timeout=timeout,
                      metadata=self._call_metadata())
        except grpc.RpcError as e:
            _raise_typed(e)

    @classmethod
    def shutdown_pool(cls) -> None:
        with cls._pool_lock:
            for ch in cls._pool.values():
                ch.close()
            cls._pool.clear()
