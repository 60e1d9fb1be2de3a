"""The validation sidecar service: one device fabric, many peers.

PAPER.md's north-star deployment shape — the TPU commit path behind a
pluggable-validation boundary, "a new BCCSP-style provider shipping
signature batches over gRPC" — realized over the repo's framed-RPC
transport (``comm.rpc``, the gRPC analog, mTLS included).  Before
this module every ``PeerChannel`` owned its own validator device
lane, so N channels × M peers meant N×M lanes contending for one
chip; the sidecar inverts that: ONE process owns the mesh-resolved
device machinery and serves ``validate`` bidi-streams to any number
of peer processes.

Flow per connection:

* the client's first frame registers a **tenant** (channel id +
  weight); the server answers a welcome frame;
* every subsequent frame is one block's signature batch
  (``sidecar/wire.py``), admitted to the tenant's BOUNDED queue in
  the weighted-deficit-round-robin scheduler
  (``sidecar/scheduler.py``) — a full queue answers a typed BUSY
  frame, never a dropped request or an unbounded buffer;
* a single dispatcher task drains cross-tenant batches of up to
  ``coalesce`` requests and launches them as ONE padded device
  dispatch through ``ops.p256v3.verify_launch_many`` — the first time
  the coalescing path merges genuinely concurrent traffic — then
  streams each batch's verdict vector back on its tenant's stream.

A dispatch failure answers each affected request with a typed ERROR
frame (the peer re-verifies those blocks locally and latches its
degrade machinery); it never tears the stream down.  ``verify_fn``
is injectable so crypto-free tests and toy fabrics reuse the whole
service unchanged.

Observability: ``sidecar_queue_depth{tenant}`` /
``sidecar_tenant_share{tenant}`` / ``sidecar_tenant_deficit{tenant}``
gauges and ``sidecar_queue_age_seconds{tenant}`` /
``sidecar_busy_total{tenant}`` (scheduler),
``sidecar_request_seconds{tenant,stage}`` histograms (queue_wait /
dispatch / total), ``sidecar_requests_total{tenant,status}``,
``sidecar_coalesce_occupancy{unit}``, tracer span trees per request
(queue_wait + dispatch children) in the ``sidecar`` flight-recorder
NAMESPACE — their own ring, so request numbering never collides with
peer block numbers in a colocated process
(``/trace?ns=sidecar&block=N``) — and ``health_check`` for
``/healthz``.  When a request carries a ``trace`` context
(``wire.py``), the finished subtree ships back in the response
header and the client stitches it under the peer's block root with
clock-offset alignment — one block's waterfall spans both processes.

Chaos hooks: ``sidecar.request`` fires at admission,
``sidecar.dispatch`` inside the coalesced device dispatch, and every
frame send passes ``rpc.frame`` (comm.rpc) — a seeded FaultPlan can
cut, delay or fail the link end to end.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
from concurrent.futures import ThreadPoolExecutor

from fabric_tpu import faults as _faults
from fabric_tpu.comm.rpc import RpcServer
from fabric_tpu.sidecar import wire
from fabric_tpu.sidecar.scheduler import Request, WeightedScheduler

_log = logging.getLogger("fabric_tpu.sidecar")

#: suggested client backoff base when BUSY (advisory; the client's
#: utils.backoff.Backoff owns the actual cadence)
BUSY_RETRY_MS = 20.0

#: suggested retry-after while a tenant is in autopilot SHED mode —
#: much longer than a transient queue-full: the controller is telling
#: this tenant to back off until its burn clears
SHED_RETRY_MS = 250.0


class SidecarServer:
    """See module docstring.

    ``verify_fn(itemsets) -> list[list[bool]]`` runs on the device
    executor thread; the default routes through the mesh-resolved
    ``ops.p256v3`` production dispatch (``mesh_devices`` /
    ``verify_chunk`` / ``recode_device`` mean exactly what they mean
    on ``BlockValidator``)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 mesh_devices: int = 0, verify_chunk: int = 0,
                 recode_device: bool = False, queue_blocks: int = 8,
                 coalesce: int = 4, quantum: int | None = None,
                 ssl_ctx=None, verify_fn=None, registry=None,
                 tracer=None, autopilot=None, mesh_topology=None):
        self.host, self.port = host, port
        self.mesh_devices = int(mesh_devices)
        # declarative mesh topology (parallel.topology.MeshTopology):
        # when configured it wins over the bare mesh_devices count and
        # may span jax.distributed processes
        self.mesh_topology = mesh_topology
        self.verify_chunk = int(verify_chunk)
        self.recode_device = bool(recode_device)
        self.coalesce = max(1, int(coalesce))
        self.mesh = None
        self._verify_fn = verify_fn
        # optional traffic autopilot (fabric_tpu/control): hellos
        # report tenant weights so the controller knows each tenant's
        # declared restore target for its re-weight rule
        self.autopilot = autopilot
        self._rpc = RpcServer(host, port, ssl_ctx=ssl_ctx)
        if tracer is None:
            from fabric_tpu.observe import global_tracer

            tracer = global_tracer()
        self.tracer = tracer
        kw = {} if quantum is None else {"quantum": int(quantum)}
        self.scheduler = WeightedScheduler(
            queue_limit=queue_blocks, registry=registry,
            clock=tracer.clock, **kw
        )
        if registry is None:
            from fabric_tpu.ops_metrics import global_registry

            registry = global_registry()
        self._req_hist = registry.histogram(
            "sidecar_request_seconds",
            "per-request sidecar time (s) by tenant and stage",
        )
        self._req_ctr = registry.counter(
            "sidecar_requests_total",
            "sidecar validate requests by tenant and outcome",
        )
        self._tenants_gauge = registry.gauge(
            "sidecar_tenants", "tenant connections currently attached"
        )
        self._coalesce_hist = registry.histogram(
            "sidecar_coalesce_occupancy",
            "cross-tenant batches merged per device dispatch "
            "(unit=requests) and their total cost (unit=signatures)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 256, 1024, 4096,
                     float("inf")),
        )
        # ONE device lane: the chip serializes dispatches anyway, and a
        # single executor thread keeps verify_launch_many calls ordered
        self._device = ThreadPoolExecutor(
            1, thread_name_prefix="fabtpu-sidecar-dev"
        )
        self._work = asyncio.Event()
        self._dispatcher: asyncio.Task | None = None
        self._conns = 0
        self._req_counter = 0  # tracer "block" numbers for requests
        self._stopped = False
        # runtime re-knobbing (the sidecar-local autopilot's
        # actuators): latched here, applied at the next
        # dispatcher-drain boundary — a coalesced group is always
        # built AND dispatched under one knob vector.  The latch is
        # LOCKED: a bare read-then-clear would drop a set_* landing
        # from the controller thread between the dispatcher's read
        # and its None store, leaving the controller's knob state and
        # the live dispatch permanently disagreeing.
        self._knob_lock = threading.Lock()
        self._pending_coalesce: int | None = None
        self._pending_verify_chunk: int | None = None

    # -- runtime re-knobbing (autopilot actuators) -------------------------

    def set_coalesce(self, n: int) -> None:
        """Request a new cross-tenant coalescing cap, applied at the
        next dispatcher-drain boundary (before the next
        ``next_batch`` pop — never between a batch's pop and its
        dispatch).  Values < 1 clamp to 1 (a dispatch always carries
        at least one request)."""
        with self._knob_lock:
            self._pending_coalesce = max(1, int(n))

    def set_verify_chunk(self, n: int) -> None:
        """Request a new device microbatch chunk for the sidecar's OWN
        dispatch, applied at the same drain boundary.  0 =
        monolithic."""
        with self._knob_lock:
            self._pending_verify_chunk = max(0, int(n))

    def _apply_pending_knobs(self) -> None:
        with self._knob_lock:
            c, self._pending_coalesce = self._pending_coalesce, None
            v, self._pending_verify_chunk = (
                self._pending_verify_chunk, None,
            )
        if c is not None and c != self.coalesce:
            _log.info("sidecar coalesce re-knobbed %d -> %d",
                      self.coalesce, c)
            self.coalesce = c
        if v is not None and v != self.verify_chunk:
            _log.info("sidecar verify_chunk re-knobbed %d -> %d",
                      self.verify_chunk, v)
            self.verify_chunk = v

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "SidecarServer":
        if self._verify_fn is None:
            topo = self.mesh_topology
            if topo is not None and topo.configured:
                self.mesh = topo.resolve()
            elif self.mesh_devices:
                from fabric_tpu.parallel.mesh import resolve_mesh

                self.mesh = resolve_mesh(self.mesh_devices)
        self._rpc.register("validate", self._on_validate)
        await self._rpc.start()
        self.port = self._rpc.port
        self._stopped = False
        # strong ref + cancelled on stop (FT008 discipline)
        self._dispatcher = asyncio.ensure_future(self._dispatch_loop())
        _log.info("validation sidecar serving on %s:%d (coalesce=%d, "
                  "queue_blocks=%d)", self.host, self.port,
                  self.coalesce, self.scheduler.queue_limit)
        return self

    async def stop(self) -> None:
        self._stopped = True
        if self._dispatcher is not None:
            self._dispatcher.cancel()
            await asyncio.gather(self._dispatcher, return_exceptions=True)
            self._dispatcher = None
        await self._rpc.stop()
        self._device.shutdown(wait=False)

    def health_check(self):
        """/healthz checker: None while serving, a reason otherwise.
        ANY tenant pinned at its queue bound is reported — that tenant
        is riding BUSY→CPU-fallback right now, and one idle neighbor
        must not mask a wedged fabric."""
        if self._stopped or self._rpc._server is None:
            return "sidecar rpc server down"
        limit = self.scheduler.queue_limit
        pinned = [
            name for name, s in self.scheduler.stats().items()
            if s["depth"] >= limit
        ]
        if pinned:
            return (
                f"tenant queue(s) full ({', '.join(pinned)}) — device "
                "fabric saturated or wedged; affected tenants are "
                "being pushed back (BUSY)"
            )
        return None

    # -- the validate stream ----------------------------------------------

    async def _on_validate(self, stream) -> None:
        try:
            hello_raw = await stream.__anext__()
        except StopAsyncIteration:
            return  # opened and closed without a hello
        try:
            hello = json.loads(hello_raw)
            tenant = str(hello["tenant"])
            weight = float(hello.get("weight", 1.0))
        except (ValueError, KeyError, TypeError) as e:
            await stream.error(f"bad hello: {e}")
            return
        try:
            self.scheduler.register(tenant, weight)  # raises on w <= 0
        except ValueError as e:
            await stream.error(f"bad hello: {e}")
            return
        if self.autopilot is not None:
            self.autopilot.observe_hello(tenant, weight)
        self._conns += 1
        self._tenants_gauge.set(self._conns)
        # everything past registration runs under the unregister
        # finally — a welcome send that dies (client gone, injected
        # rpc.frame fault) must not leak the tenant ref
        try:
            await stream.send(json.dumps(
                {"ok": True, "tenant": tenant, "coalesce": self.coalesce}
            ).encode())
            async for payload in stream:
                if _faults.plan() is not None:
                    await _faults.afire("sidecar.request", tenant=tenant)
                if payload[:1] == b"{":
                    # in-stream RE-HELLO (request frames always lead
                    # with a u32 header length, whose first byte is 0
                    # for any sane header — a raw JSON object cannot
                    # collide): a weight change updates the live
                    # registration in place, deficit and trailing
                    # stats preserved, no disconnect required
                    err = self._re_hello(tenant, payload)
                    if err is not None:
                        await stream.error(err)
                        return
                    await stream.send(json.dumps(
                        {"ok": True, "tenant": tenant,
                         "weight": self.scheduler.weight(tenant),
                         "rehello": True}
                    ).encode())
                    continue
                try:
                    hdr, items = wire.decode_request(payload)
                except (ValueError, KeyError) as e:
                    await stream.error(f"bad request: {e}")
                    return
                seq = int(hdr["seq"])
                trace = hdr.get("trace")
                extra = {}
                if isinstance(trace, dict):
                    # propagated peer trace context: root this
                    # request's queue_wait/dispatch story under it so
                    # the finished subtree ships back stitchable
                    extra = {
                        "peer_block": trace.get("block"),
                        "peer_root": trace.get("root"),
                    }
                # ns="sidecar": request trees live in their own
                # flight-recorder ring, so a colocated deployment's
                # request numbering can neither evict real block trees
                # nor collide with them at /trace?block=N
                root = self.tracer.begin_block(
                    self._next_req_id(), ns="sidecar",
                    channel=f"sidecar:{tenant}", seq=seq, **extra,
                )
                req = Request(tenant=tenant, seq=seq, items=items,
                              stream=stream, root=root,
                              trace=trace if isinstance(trace, dict)
                              else None,
                              t_enqueue=self.tracer.clock())
                if not self.scheduler.submit(req):
                    shed = self.scheduler.is_shed(tenant)
                    self._req_ctr.add(
                        1, tenant=tenant,
                        status="shed" if shed else "busy",
                    )
                    self.tracer.set_attrs(root, busy=True,
                                          **({"shed": True} if shed
                                             else {}))
                    self.tracer.finish_block(root)
                    # shed mode's retry-after is deliberately long —
                    # the autopilot is telling this tenant to back off
                    # until its burn clears, not to hammer a full queue
                    await stream.send(wire.encode_busy(
                        seq, SHED_RETRY_MS if shed else BUSY_RETRY_MS
                    ))
                    continue
                self._work.set()
        finally:
            self._conns -= 1
            self._tenants_gauge.set(self._conns)
            orphans = self.scheduler.unregister(tenant)
            for req in orphans:
                # their reply stream is gone; account them so a storm
                # of disappearing tenants is visible
                self._req_ctr.add(1, tenant=req.tenant, status="dropped")
                self.tracer.finish_block(req.root)

    def _re_hello(self, tenant: str, payload: bytes) -> str | None:
        """In-stream weight update; → error text or None on success.
        The tenant name must match the stream's registration — one
        connection cannot re-weight another tenant."""
        try:
            hello = json.loads(payload)
            who = str(hello["tenant"])
            weight = float(hello.get("weight", 1.0))
        except (ValueError, KeyError, TypeError) as e:
            return f"bad re-hello: {e}"
        if who != tenant:
            return (
                f"bad re-hello: stream is registered as {tenant!r}, "
                f"not {who!r}"
            )
        try:
            self.scheduler.set_weight(tenant, weight)
        except ValueError as e:
            return f"bad re-hello: {e}"
        if self.autopilot is not None:
            self.autopilot.observe_hello(tenant, weight)
        return None

    def _next_req_id(self) -> int:
        self._req_counter += 1
        return self._req_counter

    # -- the dispatcher ----------------------------------------------------

    async def _dispatch_loop(self) -> None:
        loop = asyncio.get_event_loop()
        while True:
            await self._work.wait()
            self._work.clear()
            while True:
                # drain boundary: adopt any latched knob values before
                # the next batch is built (set_coalesce /
                # set_verify_chunk — the sidecar-local autopilot's
                # actuation point)
                self._apply_pending_knobs()
                batch = self.scheduler.next_batch(self.coalesce)
                if not batch:
                    break
                self._coalesce_hist.observe(len(batch), unit="requests")
                self._coalesce_hist.observe(
                    sum(r.cost for r in batch), unit="signatures"
                )
                t0 = self.tracer.clock()
                try:
                    verdicts = await loop.run_in_executor(
                        self._device, self._dispatch_traced,
                        [r.items for r in batch], batch[0].root,
                    )
                    t1 = self.tracer.clock()
                    await self._answer(batch, verdicts, t0, t1)
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    # a dispatch failure answers typed errors (clients
                    # re-verify locally); anything unexpected escaping
                    # the ANSWER path must not kill this task either —
                    # a dead dispatcher would silently halt every
                    # tenant until process restart
                    _log.warning(
                        "sidecar dispatch of %d batch(es) failed: %s — "
                        "answering typed errors (clients re-verify "
                        "locally)", len(batch), e,
                    )
                    try:
                        await self._answer_error(batch, e)
                    except asyncio.CancelledError:
                        raise
                    except Exception as e2:
                        _log.warning(
                            "sidecar error-answer path failed too (%s) "
                            "— dropping %d response(s); affected "
                            "clients time out and fall back locally",
                            e2, len(batch),
                        )
                        for req in batch:
                            self._req_ctr.add(1, tenant=req.tenant,
                                              status="dropped")
                            self.tracer.finish_block(req.root)

    def _dispatch_traced(self, itemsets: list, root) -> list:
        """Executor-thread shim: adopt the coalesced group's LEADER
        request tree as the thread-current span for the device verify,
        so the launch ledger's ``dev:*`` child spans (and its
        histogram exemplars) attach to the request the dispatch was
        built for — the sidecar's /trace?ns=sidecar waterfall then
        carries the device lane too."""
        tok = self.tracer.attach(root) if root is not None else None
        try:
            return self._verify_batch(itemsets)
        finally:
            if root is not None:
                self.tracer.detach(tok)

    def _verify_batch(self, itemsets: list) -> list:
        _faults.fire("sidecar.dispatch", n=len(itemsets))
        if self._verify_fn is not None:
            return self._verify_fn(itemsets)
        return self._device_verify(itemsets)

    def _device_verify(self, itemsets: list) -> list:
        """The production path: ONE coalesced padded dispatch over the
        mesh for the whole cross-tenant group, then per-batch fetches."""
        from fabric_tpu.ops import p256v3

        handles = p256v3.verify_launch_many(
            itemsets, chunk=self.verify_chunk or None, mesh=self.mesh,
            recode_device=self.recode_device,
        )
        return [[bool(v) for v in h()] for h in handles]

    async def _answer(self, batch: list, verdicts: list,
                      t0: float, t1: float) -> None:
        for req, ok in zip(batch, verdicts):
            self._req_hist.observe(t0 - req.t_enqueue, tenant=req.tenant,
                                  stage="queue_wait")
            self._req_hist.observe(t1 - t0, tenant=req.tenant,
                                  stage="dispatch")
            self._req_hist.observe(t1 - req.t_enqueue, tenant=req.tenant,
                                  stage="total")
            self.tracer.add("queue_wait", req.t_enqueue, t0,
                            parent=req.root)
            self.tracer.add("dispatch", t0, t1, parent=req.root,
                            coalesced=len(batch), n_sigs=req.cost)
            sent = await self._send(
                req, wire.encode_response(req.seq, ok,
                                          remote=self._remote(req))
            )
            self._req_ctr.add(1, tenant=req.tenant,
                              status="ok" if sent else "dropped")
            self.tracer.finish_block(req.root)

    def _remote(self, req: Request) -> dict | None:
        """The finished request subtree + send/receive timestamps the
        client stitches from — only built when the request carried a
        trace context (the peer asked) and tracing is on here."""
        if req.trace is None or req.root is None:
            return None
        # close the root NOW so the shipped tree has a complete
        # window; finish_block tolerates a pre-set t1 (ring append
        # and watchdog run there as usual)
        self.tracer.end(req.root)
        return {
            "spans": req.root.to_dict(0.0),
            "t_rx": round(req.t_enqueue * 1000.0, 3),
            "t_tx": round(self.tracer.clock() * 1000.0, 3),
        }

    async def _answer_error(self, batch: list, err: Exception) -> None:
        msg = f"{type(err).__name__}: {err}"
        for req in batch:
            await self._send(req, wire.encode_error(req.seq, msg))
            self._req_ctr.add(1, tenant=req.tenant, status="error")
            self.tracer.set_attrs(req.root, error=msg[:120])
            self.tracer.finish_block(req.root)

    @staticmethod
    async def _send(req: Request, payload: bytes) -> bool:
        try:
            await req.stream.send(payload)
            return True
        except (ConnectionError, OSError, RuntimeError, EOFError) as e:
            _log.debug("tenant %s went away before its response (%s)",
                       req.tenant, e)
            return False
