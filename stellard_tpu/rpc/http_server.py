"""HTTP JSON-RPC door.

Reference: src/ripple/http (async HTTP server framework) bound to the RPC
handler table by RPCHTTPServer (Application.cpp:325); request format is
JSON-RPC 1.0-style {"method": ..., "params": [{...}]} and responses wrap
the handler result as {"result": {..., "status": "success"|"error"}}
(reference: RPCServerHandler::processRequest).

asyncio protocol implementation — no external HTTP library.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Optional

from .errors import RPCError
from .handlers import HANDLERS, Context, Role, dispatch

__all__ = ["HttpRpcServer", "process_http_request"]

_MAX_BODY = 10 * 1024 * 1024

# the lag probe on the door's own event loop: a tick every LAG_TICK_S
# measures how late it ran; a tick LAG_SPAN_MIN_S or more late is an
# `rpc.loop_lag` span (the time work waited for the door, measured
# where the work waits)
LAG_TICK_S = 0.050
LAG_SPAN_MIN_S = 0.025

# back-pressure at the door of a NETWORKED node: while the open ledger
# has grown by its soft cap ([txq]), or faster than evenly over the
# protocol's shortest round (`TxQ.open_has_room`), a client's `submit`
# is held, up to SUBMIT_HOLD_S, until there is room; then it is admitted
# as ever. A door answers in milliseconds, and what it admits costs
# every validator time INSIDE the round, so clients that send as fast as
# they are answered swelled the open ledger, the round behind it and the
# next one more (PERF.md section 6, PR 32). Fee escalation prices the
# same cap, but clients that all pay far more are not slowed by it, and
# a held client is refused nothing, so an account's sequence stays in
# order. A standalone node never holds: nothing closes its ledger but
# its client.
SUBMIT_HOLD_S = 10.0
SUBMIT_HOLD_POLL_S = 0.02


def process_http_request(node, body: bytes, role: Role = Role.ADMIN,
                         client_ip: str = "",
                         seen: Optional[dict] = None) -> dict:
    """Decode one JSON-RPC request body → response object. Non-admin
    requests charge the client's resource balance (FEE_*_RPC schedule);
    a client past the drop line gets rpcSLOW_DOWN until it decays.
    With ``seen`` (the door's counters), the method name is left in
    ``seen["method"]``."""
    from .handlers import charge_rpc_client

    try:
        req = json.loads(body)
    except ValueError:
        refused = charge_rpc_client(node, client_ip, None, role)  # charged
        err = refused or RPCError("invalidParams", "malformed JSON").to_json()
        return {"result": err | {"status": "error"}}
    method = req.get("method")
    params_list = req.get("params") or [{}]
    params = params_list[0] if isinstance(params_list, list) and params_list else {}
    if not isinstance(params, dict):
        params = {}
    if not isinstance(method, str):
        refused = charge_rpc_client(node, client_ip, None, role)
        err = refused or RPCError("unknownCmd").to_json()
        return {"result": err | {"status": "error"}}
    if seen is not None:
        seen["method"] = method
    refused = charge_rpc_client(node, client_ip, method, role)
    if refused is not None:
        result = refused | {"status": "error"}
        out = {"result": result}
        if "id" in req:
            out["id"] = req["id"]
        return out
    result = dispatch(Context(node=node, params=params, role=role), method)
    result["status"] = "error" if "error" in result else "success"
    from .handlers import rpc_warning

    warn = rpc_warning(node, client_ip, role)
    if warn is not None:
        result["warning"] = warn
    out = {"result": result}
    if "id" in req:
        out["id"] = req["id"]
    return out


def _role_for_peer(node, writer) -> Role:
    """ADMIN only for connections from [rpc_admin_allow] source IPs
    (reference: RPCHandler role gating by admin-allowed IP)."""
    peer = writer.get_extra_info("peername")
    ip = peer[0] if peer else ""
    return Role.ADMIN if ip in node.config.admin_ips else Role.GUEST


class HttpRpcServer:
    """Minimal threaded asyncio HTTP/1.1 server for the RPC door."""

    def __init__(self, node, host: str = "127.0.0.1", port: int = 0,
                 ssl_context=None):
        self._ssl = ssl_context  # reference [rpc_secure] (RPCDoor SSL)
        self.node = node
        self.host = host
        self.port = port
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self._server = None
        # the door seen from inside (get_json(), the `rpc` collector
        # hook): every request counts; one in `1/sample` is an
        # `rpc.request` span. Only the loop thread writes these.
        self.tracer = getattr(node, "tracer", None)
        sample = self.tracer.sample if self.tracer is not None else 0.0
        self._span_every = max(1, round(1.0 / sample)) if sample > 0 else 0
        self.requests = 0
        self.errors = 0
        self.busy_s = 0.0
        self.by_method: dict[str, int] = {}
        self.lag_ticks = 0
        self.lag_late_ticks = 0
        self.lag_s = 0.0
        self.submit_holds = 0
        self.submit_hold_s = 0.0

    # -- protocol ---------------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                header = await reader.readuntil(b"\r\n\r\n")
                lines = header.decode("latin-1").split("\r\n")
                request_line = lines[0]
                headers = {}
                for line in lines[1:]:
                    if ":" in line:
                        k, v = line.split(":", 1)
                        headers[k.strip().lower()] = v.strip()
                length = int(headers.get("content-length", 0))
                if length > _MAX_BODY:
                    writer.write(b"HTTP/1.1 413 Payload Too Large\r\n\r\n")
                    await writer.drain()
                    return
                body = await reader.readexactly(length) if length else b""
                t_read = time.perf_counter()
                seen = {"method": "GET"}
                failed = False
                status_line = b"HTTP/1.1 200 OK\r\n"
                ctype = b"Content-Type: application/json\r\n"
                if request_line.startswith("GET"):
                    path = (
                        request_line.split(" ", 2)[1]
                        if " " in request_line else "/"
                    )
                    if path.split("?", 1)[0] == "/metrics":
                        # Prometheus exposition door (text format 0.0.4,
                        # node/metrics.py prometheus_text). Resource-
                        # priced like any other RPC: a scraper hammering
                        # the door charges its client balance and gets
                        # 429 until it decays (admin IPs exempt).
                        from .handlers import charge_rpc_client

                        peer = writer.get_extra_info("peername")
                        refused = charge_rpc_client(
                            self.node, peer[0] if peer else "",
                            "metrics", _role_for_peer(self.node, writer),
                        )
                        if refused is not None:
                            status_line = (
                                b"HTTP/1.1 429 Too Many Requests\r\n"
                            )
                            failed = True
                            payload = b"slow down\n"
                            ctype = b"Content-Type: text/plain\r\n"
                        else:
                            payload = self._metrics_payload()
                            ctype = (
                                b"Content-Type: text/plain; "
                                b"version=0.0.4; charset=utf-8\r\n"
                            )
                    else:
                        payload = b'{"status": "ok"}'
                else:
                    if b'"submit"' in body:
                        await self._hold_submit()
                        t_read = time.perf_counter()  # busy_s: work only
                    peer = writer.get_extra_info("peername")
                    reply = process_http_request(
                        self.node, body,
                        _role_for_peer(self.node, writer),
                        client_ip=peer[0] if peer else "",
                        seen=seen,
                    )
                    failed = reply["result"].get("status") == "error"
                    payload = json.dumps(reply).encode()
                writer.write(
                    status_line + ctype
                    + f"Content-Length: {len(payload)}\r\n".encode()
                    + b"Connection: keep-alive\r\n\r\n"
                    + payload
                )
                await writer.drain()
                self._note_request(seen["method"], failed, t_read,
                                   len(body), len(payload))
                if headers.get("connection", "").lower() == "close":
                    break
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.LimitOverrunError):
            pass
        finally:
            writer.close()

    async def _hold_submit(self) -> None:
        """Hold a `submit` while the open ledger has no room for it
        (see SUBMIT_HOLD_S). The body is parsed once, behind this, so
        the method is read off its bytes: a request that only mentions
        "submit" is delayed like one, and no worse."""
        node = self.node
        txq = getattr(node, "txq", None)
        if (txq is None or not txq.enabled
                or getattr(node, "overlay", None) is None):
            return
        lm = node.ledger_master
        if txq.open_has_room(lm):
            return
        t0 = time.perf_counter()
        deadline = t0 + SUBMIT_HOLD_S
        while time.perf_counter() < deadline:
            await asyncio.sleep(SUBMIT_HOLD_POLL_S)
            if txq.open_has_room(lm):
                break
        t1 = time.perf_counter()
        self.submit_holds += 1
        self.submit_hold_s += t1 - t0
        if self._span_every and self.submit_holds % self._span_every == 0:
            self.tracer.complete("rpc.submit_hold", "rpc", t0, t1)

    def _note_request(self, method, failed: bool, t_read: float,
                      bytes_in: int, bytes_out: int) -> None:
        """One answered request: body read to response written."""
        t_done = time.perf_counter()
        self.requests += 1
        self.busy_s += t_done - t_read
        if failed:
            self.errors += 1
        # names come from outside: only the handler table's (and GET)
        # get a counter of their own
        if method != "GET" and method not in HANDLERS:
            method = "?"
        self.by_method[method] = self.by_method.get(method, 0) + 1
        if self._span_every and self.requests % self._span_every == 0:
            self.tracer.complete(
                "rpc.request", "rpc", t_read, t_done, method=method,
                status="error" if failed else "ok",
                bytes_in=bytes_in, bytes_out=bytes_out)

    def _lag_tick(self, due: float) -> None:
        """Runs on the door's loop every LAG_TICK_S: how late is the
        time the loop could not run (a handler, a collection, a close
        holding the interpreter)."""
        now = time.perf_counter()
        late = now - due
        self.lag_ticks += 1
        if late >= LAG_SPAN_MIN_S:
            self.lag_late_ticks += 1
            self.lag_s += late
            self.tracer.complete("rpc.loop_lag", "rpc", due, now)
        nxt = max(now, due) + LAG_TICK_S
        self._loop.call_later(max(0.0, nxt - time.perf_counter()),
                              self._lag_tick, nxt)

    def get_json(self) -> dict:
        """The door's counters (``get_counts.rpc_door``, the ``rpc``
        collector hook: ``rpc.requests``, ``rpc.busy_s``, ``rpc.errors``,
        ``rpc.lag_s`` on ``/metrics``)."""
        return {
            "requests": self.requests,
            "errors": self.errors,
            "busy_s": round(self.busy_s, 6),
            "lag_s": round(self.lag_s, 6),
            "lag_ticks": self.lag_ticks,
            "lag_late_ticks": self.lag_late_ticks,
            "submit_holds": self.submit_holds,
            "submit_hold_s": round(self.submit_hold_s, 6),
            "by_method": dict(self.by_method),
        }

    def _metrics_payload(self) -> bytes:
        """One /metrics scrape: every collector instrument plus the
        health verdict as a rank gauge (0=ok 1=warn 2=critical)."""
        extra = {}
        health = getattr(self.node, "health", None)
        if health is not None:
            from ..node.health import _RANK

            extra["health_status"] = _RANK.get(health.status, 0)
        try:
            text = self.node.collector.prometheus_text(extra_gauges=extra)
        except Exception:  # noqa: BLE001 — a scrape must not kill the door
            text = ""
        return text.encode("utf-8")

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "HttpRpcServer":
        from ..node.tracer import THREAD_ROLES

        self._thread = threading.Thread(
            target=THREAD_ROLES.wrap("door", self._run), daemon=True,
            name="rpc-http")
        self._thread.start()
        self._started.wait(timeout=10)
        return self

    def _run(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)

        async def boot():
            self._server = await asyncio.start_server(
                self._handle, self.host, self.port, limit=_MAX_BODY,
                ssl=self._ssl,
            )
            self.port = self._server.sockets[0].getsockname()[1]
            if self.tracer is not None and self.tracer.enabled:
                self._lag_tick(time.perf_counter())
            self._started.set()

        self._loop.run_until_complete(boot())
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    def stop(self) -> None:
        if self._loop and self._loop.is_running():
            def _shutdown():
                if self._server:
                    self._server.close()
                self._loop.stop()

            self._loop.call_soon_threadsafe(_shutdown)
        if self._thread:
            self._thread.join(timeout=5)
