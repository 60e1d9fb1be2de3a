"""Gateway service: the v2.4+ single-endpoint transaction API.

Reference: internal/pkg/gateway — Evaluate (endorse.go sibling,
evaluate.go:23), Endorse (endorse.go:170, returns a PREPARED
transaction for the client to sign — the gateway never holds client
keys), Submit (submit.go:31, orderer broadcast incl. retry over the
orderer set), CommitStatus (commitstatus.go:26, ledger commit
notifications), ChaincodeEvents (event stream from committed blocks).

The endorsement plan comes from the discovery layouts
(fabric_tpu.discovery.layouts_for_policy ==
discovery/endorsement/endorsement.go:84 PeersForEndorsement); per-org
peers come from the node's PeerRegistry.
"""

from __future__ import annotations

import asyncio
import json
import logging

from fabric_tpu import protoutil
from fabric_tpu.comm.rpc import RpcClient
from fabric_tpu.discovery import DiscoveryService, layouts_for_policy
from fabric_tpu.peer import txassembly as txa

from fabric_tpu.observe import txflow as _txflow
from fabric_tpu.peer.endorser import Endorser
from fabric_tpu.protos import common_pb2, proposal_pb2, transaction_pb2

_log = logging.getLogger("fabric_tpu.gateway")


class GatewayError(Exception):
    def __init__(self, status: int, msg: str):
        super().__init__(msg)
        self.status = status


def _envelope_tx_id(env_bytes: bytes) -> str:
    """tx_id from a signed Envelope's channel header, for the
    tx-flow submit/broadcast stamps — contained: an unparsable
    envelope is the orderer's problem to reject, not the journal's."""
    try:
        env = protoutil.unmarshal(common_pb2.Envelope, env_bytes)
        payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
        ch = protoutil.unmarshal(
            common_pb2.ChannelHeader, payload.header.channel_header
        )
        return ch.tx_id
    except Exception:
        return ""


class Gateway:
    """Bound to one PeerNode; registered on its RPC server."""

    def __init__(self, node):
        self.node = node

    # -- helpers -----------------------------------------------------------

    def _parse_proposal(self, req: bytes):
        signed = proposal_pb2.SignedProposal()
        signed.ParseFromString(req)
        prop = protoutil.unmarshal(proposal_pb2.Proposal, signed.proposal_bytes)
        header = protoutil.unmarshal(common_pb2.Header, prop.header)
        ch = protoutil.unmarshal(common_pb2.ChannelHeader, header.channel_header)
        ext = protoutil.unmarshal(
            proposal_pb2.ChaincodeHeaderExtension, ch.extension
        )
        chan = self.node.channels.get(ch.channel_id)
        if chan is None:
            raise GatewayError(404, f"not joined to {ch.channel_id}")
        return signed, prop, ch, ext.chaincode_id.name, chan

    async def _endorse_local(self, chan, signed):
        # endorse_signer: the node's batched ESCC sign provider when
        # sign_device armed one (peer/signlane) — concurrent client
        # streams then fill device sign lanes; the serial signer
        # otherwise (bit-equal signatures either way, RFC 6979)
        endorser = chan.make_endorser(
            self.node.msp,
            getattr(self.node, "endorse_signer", None) or self.node.signer,
            self.node.runtime,
        )
        loop = asyncio.get_event_loop()
        async with chan.commit_lock.reader():
            return await loop.run_in_executor(
                None, endorser.process_proposal, signed
            )

    async def _endorse_remote(self, host, port, req: bytes):
        """One remote Endorse RPC; transport/parse failures surface as
        a retryable GatewayError(503) so the layout loop fails over to
        the next layout instead of tearing the whole Endorse down."""
        try:
            cli = RpcClient(
                host, port,
                ssl_ctx=self.node.tls.client_ctx()
                if getattr(self.node, "tls", None) else None,
            )
            await cli.connect()
            try:
                raw = await cli.unary("Endorse", req)
            finally:
                await cli.close()
            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            return pr
        except GatewayError:
            raise
        except Exception as e:
            raise GatewayError(
                503, f"remote endorse {host}:{port} failed: {e}"
            ) from e

    # -- service methods ---------------------------------------------------

    async def evaluate(self, req: bytes) -> bytes:
        """Run the proposal on THIS peer; return the chaincode Response
        (no ordering) — read-only queries."""
        signed, _, _, _, chan = self._parse_proposal(req)
        result = await self._endorse_local(chan, signed)
        pr = result.response
        if pr.response.status >= 400 or not pr.payload:
            return pr.response.SerializeToString()
        # the chaincode's Response lives inside prp.extension
        prp = protoutil.unmarshal(
            proposal_pb2.ProposalResponsePayload, pr.payload
        )
        cca = protoutil.unmarshal(proposal_pb2.ChaincodeAction, prp.extension)
        return cca.response.SerializeToString()

    async def endorse(self, req: bytes) -> bytes:
        """Collect endorsements per the discovery layout; return the
        PREPARED transaction payload for the client to sign.

        Endorsement failures (simulation errors, a 429 from a full
        sign batcher, remote transport failures wrapped as 503) fail
        the CURRENT layout and the loop tries the next one; when no
        layout survives, the last error propagates — a 429 tells the
        client to back off briefly and retry, a 503 to try another
        gateway peer."""
        signed, prop, ch, cc_name, chan = self._parse_proposal(req)
        # tx-flow journal: the endorse stage opens the per-tx record
        # (observe/txflow.py) — a failed endorsement terminates the
        # flow, a prepared one waits for submit/inclusion
        _txflow.endorse_begin(ch.tx_id)
        try:
            payload = await self._endorse_inner(
                req, signed, prop, ch, cc_name, chan
            )
        except BaseException:
            _txflow.endorse_end(ch.tx_id, ok=False)
            raise
        _txflow.endorse_end(ch.tx_id)
        return payload

    async def _endorse_inner(self, req, signed, prop, ch, cc_name,
                             chan) -> bytes:
        info = chan.validator.policies.info(cc_name)
        if info is None:
            raise GatewayError(404, f"no validation info for {cc_name}")
        layouts = layouts_for_policy(info.policy)
        my_org = self.node.signer.msp_id
        responses = []
        last_err = None
        local_res = None  # simulate locally ONCE across layout attempts
        for layout in sorted(
            layouts, key=lambda l: (my_org not in l, sum(l.values()))
        ):
            try:
                responses = []
                for org, count in sorted(layout.items()):
                    if org == my_org:
                        if local_res is None:
                            local_res = await self._endorse_local(chan, signed)
                        res = local_res
                        if res.response.response.status >= 400:
                            raise GatewayError(
                                res.response.response.status,
                                res.response.response.message,
                            )
                        responses.append(res.response)
                        count -= 1
                    peers = self.node.registry.for_org(org)
                    if count > len(peers):
                        raise GatewayError(
                            503, f"not enough peers for {org}"
                        )
                    for p in peers[:count]:
                        pr = await self._endorse_remote(p.host, p.port, req)
                        if pr.response.status >= 400:
                            raise GatewayError(pr.response.status, pr.response.message)
                        responses.append(pr)
                break
            except GatewayError as e:
                last_err = e
                responses = []
        if not responses:
            raise last_err or GatewayError(503, "no viable endorsement layout")
        payload = txa.prepare_transaction(prop, responses)
        return payload.SerializeToString()

    async def submit(self, req: bytes) -> bytes:
        """req: JSON{channel} ‖ 0x00 ‖ signed Envelope bytes → orderer
        broadcast with failover across the channel's orderer set."""
        hdr, env_bytes = req.split(b"\x00", 1)
        channel = json.loads(hdr)["channel"]
        chan = self.node.channels.get(channel)
        if chan is None:
            raise GatewayError(404, f"not joined to {channel}")
        addrs = getattr(chan, "orderer_addrs", None) or []
        if not addrs:
            raise GatewayError(503, "no orderers known for channel")
        # tx-flow journal: the envelope parse to recover tx_id is only
        # paid when the journal is armed (one global check disarmed)
        tx_id = _envelope_tx_id(env_bytes) if _txflow.enabled() else ""
        if tx_id:
            _txflow.submit_begin(tx_id)
        from fabric_tpu.ordering.node import BroadcastClient

        cli = BroadcastClient(
            list(addrs),
            ssl_ctx=self.node.tls.client_ctx()
            if getattr(self.node, "tls", None) else None,
        )
        try:
            res = await cli.broadcast(channel, env_bytes)
        finally:
            await cli.close()
        if res.get("status") != 200:
            raise GatewayError(res.get("status", 500), res.get("info", "broadcast failed"))
        if tx_id:
            _txflow.broadcast_done(tx_id)
        return json.dumps({"status": 200}).encode()

    async def commit_status(self, req: bytes) -> bytes:
        """req: JSON{channel, tx_id, timeout?} → {code, block} once the
        tx commits (ledger commit notification analog).

        The answer lands as soon as the tx is IN a block, but under
        the decoupled committer (ledger/committer.py) its writes may
        not be state-visible yet — ``applied`` is the honest
        read-your-writes bit (true iff state apply has passed the
        tx's block), alongside the channel's ``durable_height``
        (appends past the fsync fence) and ``applied_height``."""
        q = json.loads(req)
        chan = self.node.channels.get(q["channel"])
        if chan is None:
            raise GatewayError(404, f"not joined to {q['channel']}")
        deadline = asyncio.get_event_loop().time() + float(q.get("timeout", 30.0))
        txid = q["tx_id"]
        while True:
            loc = chan.ledger.blocks.get_tx_loc(txid)
            if loc is not None:
                num, txnum, code = loc
                ledger = chan.ledger
                eng = getattr(ledger, "engine", None)
                if eng is not None:
                    applied_height = (
                        int(eng.stats().get("applied_num", -1)) + 1
                    )
                else:
                    # serial commit: state apply completes inside
                    # commit_block, so applied tracks block height
                    applied_height = int(ledger.blocks.height)
                durable_height = int(
                    getattr(ledger.blocks, "synced_height",
                            ledger.blocks.height)
                )
                return json.dumps(
                    {"tx_id": txid, "code": int(code), "block": int(num),
                     "code_name": transaction_pb2.TxValidationCode.Name(int(code)),
                     "applied": applied_height > int(num),
                     "applied_height": applied_height,
                     "durable_height": durable_height}
                ).encode()
            remaining = deadline - asyncio.get_event_loop().time()
            if remaining <= 0:
                raise GatewayError(408, f"timeout waiting for {txid}")
            ev = chan._height_changed
            try:
                await asyncio.wait_for(ev.wait(), remaining)
            except asyncio.TimeoutError:
                raise GatewayError(408, f"timeout waiting for {txid}")

    async def chaincode_events(self, stream):
        """stream request: JSON{channel, chaincode, start?} → one JSON
        event per message from committed VALID txs."""
        req = json.loads(await stream.__anext__())
        chan = self.node.channels.get(req["channel"])
        if chan is None:
            await stream.error("no such channel")
            return
        want_cc = req["chaincode"]
        num = int(req.get("start", 0))
        while True:
            if num >= chan.height:
                await chan._height_changed.wait()
                continue
            blk = chan.ledger.blocks.get_block(num)
            if blk is None:
                await stream.error(
                    f"block {num} unavailable (pre-snapshot)"
                )
                return
            flags = protoutil.get_tx_filter(blk)
            for i, env_bytes in enumerate(blk.data.data):
                if i < len(flags) and flags[i] != 0:
                    continue
                try:
                    env = protoutil.unmarshal(common_pb2.Envelope, env_bytes)
                    _, _, cap, prp, cca = protoutil.extract_action(env)
                except Exception as e:
                    _log.debug(
                        "event stream: tx %d of block %d not an "
                        "endorser action: %s", i, blk.header.number, e,
                    )
                    continue
                if not cca.events:
                    continue
                ev = protoutil.unmarshal(proposal_pb2.ChaincodeEvent, cca.events)
                if ev.chaincode_id != want_cc:
                    continue
                await stream.send(json.dumps({
                    "block": num, "tx_id": ev.tx_id,
                    "event_name": ev.event_name,
                    "payload": ev.payload.hex(),
                }).encode())
            num += 1


def register(node) -> Gateway:
    """Attach gateway services to a PeerNode's RPC server.

    Unary responses are framed: 0x00 ‖ payload on success,
    0x01 ‖ JSON{status, error} on failure."""
    gw = Gateway(node)

    def unary(fn):
        async def handler(req: bytes) -> bytes:
            try:
                return b"\x00" + await fn(req)
            except GatewayError as e:
                return b"\x01" + json.dumps(
                    {"error": str(e), "status": e.status}
                ).encode()
        return handler

    node.server.register_unary("GwEvaluate", unary(gw.evaluate))
    node.server.register_unary("GwEndorse", unary(gw.endorse))
    node.server.register_unary("GwSubmit", unary(gw.submit))
    node.server.register_unary("GwCommitStatus", unary(gw.commit_status))
    node.server.register("GwChaincodeEvents", gw.chaincode_events)
    return gw


class GatewayClient:
    """SDK-side convenience over the gateway surface (the
    fabric-gateway client analog): sign → endorse → sign → submit →
    await commit."""

    def __init__(self, host: str, port: int, signer, ssl_ctx=None):
        self.host, self.port = host, port
        self.signer = signer
        self.ssl_ctx = ssl_ctx
        self._cli: RpcClient | None = None

    async def _client(self) -> RpcClient:
        if self._cli is None:
            self._cli = RpcClient(self.host, self.port, ssl_ctx=self.ssl_ctx)
            await self._cli.connect()
        return self._cli

    async def close(self):
        if self._cli is not None:
            await self._cli.close()

    @staticmethod
    def _unwrap(raw: bytes) -> bytes:
        if raw[:1] == b"\x01":
            err = json.loads(raw[1:])
            raise GatewayError(err.get("status", 500), err.get("error", ""))
        return raw[1:]

    async def evaluate(self, channel: str, chaincode: str, args: list[bytes]):
        signed, _, _ = txa.create_signed_proposal(
            self.signer, channel, chaincode, args
        )
        cli = await self._client()
        raw = self._unwrap(await cli.unary(
            "GwEvaluate", signed.SerializeToString(), timeout=120.0
        ))
        resp = proposal_pb2.Response()
        resp.ParseFromString(raw)
        return resp

    async def submit_transaction(self, channel: str, chaincode: str,
                                 args: list[bytes], wait: bool = True,
                                 transient: dict | None = None):
        """The full gateway round trip; returns (tx_id, status dict)."""
        signed, tx_id, _ = txa.create_signed_proposal(
            self.signer, channel, chaincode, args, transient=transient
        )
        cli = await self._client()
        payload_bytes = self._unwrap(
            await cli.unary(
                "GwEndorse", signed.SerializeToString(), timeout=120.0
            )
        )
        env = common_pb2.Envelope(
            payload=payload_bytes, signature=self.signer.sign(payload_bytes)
        )
        hdr = json.dumps({"channel": channel}).encode()
        self._unwrap(await cli.unary(
            "GwSubmit", hdr + b"\x00" + env.SerializeToString(), timeout=60.0
        ))
        if not wait:
            return tx_id, None
        return tx_id, await self.commit_status(channel, tx_id)

    async def commit_status(self, channel: str, tx_id: str,
                            timeout: float = 120.0) -> dict:
        """The tx's commit status once it is in a block (408 after
        ``timeout`` seconds); ``applied`` says whether its writes are
        readable yet."""
        cli = await self._client()
        raw = self._unwrap(await cli.unary(
            "GwCommitStatus",
            json.dumps({"channel": channel, "tx_id": tx_id,
                        "timeout": timeout}).encode(),
            timeout=timeout + 10.0,
        ))
        return json.loads(raw)
