"""Server-side TURN relay (webrtc/turn_client + ice relay routing).

VERDICT r4 item 5: the reference's NAT-traversal story
(README.md:65-143, xgl.yml:85-109) exists so the SERVER's media can
relay when hostNetwork is impossible.  These tests run an in-process
mock TURN server (RFC 5766 server role: Allocate with long-term auth,
CreatePermission, Send/Data indications) and prove:

1. the allocation client speaks the protocol (401 -> authenticated
   retry -> relayed address; permissions; data both ways);
2. end-to-end: a browser-role peer that ONLY talks to the relayed
   address completes ICE + DTLS and decodes SRTP media (the 'done' bar).
"""

import asyncio
import secrets
import struct

import numpy as np
import pytest

# The DTLS stack (webrtc/dtls) dlopens the system libssl.so.3 at import
# time; containers without OpenSSL 3 cannot even COLLECT this module —
# skip it cleanly so tier-1 collection stays green (CI's runners ship
# libssl.so.3 and run these tests in full).
try:
    import docker_nvidia_glx_desktop_tpu.webrtc.dtls  # noqa: F401
except OSError as _dtls_err:
    pytest.skip(f"system libssl unavailable: {_dtls_err}",
                allow_module_level=True)

from docker_nvidia_glx_desktop_tpu.webrtc import rtp, stun
from docker_nvidia_glx_desktop_tpu.webrtc.turn_client import (
    TurnAllocation, long_term_key)

from test_webrtc import OFFER_TMPL

REALM = "tpu-test"
NONCE = b"mock-nonce-1"


class MockTurnServer:
    """Minimal RFC 5766 server: one allocation per 5-tuple, long-term
    credential auth, permission enforcement on both directions."""

    def __init__(self, users: dict):
        self.users = users
        self.transport = None
        self.allocs = {}        # client addr -> (relay_transport, perms)
        self.auth_failures = 0

    async def start(self):
        loop = asyncio.get_running_loop()
        outer = self

        class Proto(asyncio.DatagramProtocol):
            def datagram_received(self, data, addr):
                asyncio.ensure_future(outer._on_client(data, addr))

        self.transport, _ = await loop.create_datagram_endpoint(
            Proto, local_addr=("127.0.0.1", 0))
        return self.transport.get_extra_info("sockname")

    def close(self):
        if self.transport is not None:
            self.transport.close()
        for relay, _ in self.allocs.values():
            relay.close()

    async def _make_relay(self, client_addr):
        loop = asyncio.get_running_loop()
        outer = self

        class Relay(asyncio.DatagramProtocol):
            def datagram_received(self, data, peer):
                relay, perms = outer.allocs[client_addr]
                if peer[0] not in perms:
                    return                       # no permission: drop
                ind = stun.StunMessage(stun.DATA_INDICATION)
                ind.add_xor_address(stun.ATTR_XOR_PEER_ADDRESS, *peer[:2])
                ind.attrs[stun.ATTR_DATA] = data
                outer.transport.sendto(ind.encode(fingerprint=False),
                                       client_addr)

        relay_tr, _ = await loop.create_datagram_endpoint(
            Relay, local_addr=("127.0.0.1", 0))
        return relay_tr

    async def _on_client(self, data, addr):
        try:
            msg = stun.StunMessage.decode(data)
        except ValueError:
            return
        if msg.mtype == stun.ALLOCATE_REQUEST:
            user = msg.username
            if user is None:
                err = stun.StunMessage(stun.ALLOCATE_ERROR, txid=msg.txid)
                err.add_error(401, "Unauthorized")
                err.attrs[stun.ATTR_REALM] = REALM.encode()
                err.attrs[stun.ATTR_NONCE] = NONCE
                self.transport.sendto(err.encode(), addr)
                return
            pw = self.users.get(user)
            key = (long_term_key(user, REALM, pw)
                   if pw is not None else None)
            if key is None or not msg.verify_integrity(key):
                self.auth_failures += 1
                err = stun.StunMessage(stun.ALLOCATE_ERROR, txid=msg.txid)
                err.add_error(431, "Integrity Check Failure")
                self.transport.sendto(err.encode(), addr)
                return
            relay_tr = await self._make_relay(addr)
            self.allocs[addr] = (relay_tr, set())
            resp = stun.StunMessage(stun.ALLOCATE_SUCCESS, txid=msg.txid)
            resp.add_xor_address(
                stun.ATTR_XOR_RELAYED_ADDRESS,
                *relay_tr.get_extra_info("sockname")[:2])
            resp.add_xor_address(stun.ATTR_XOR_MAPPED_ADDRESS, *addr[:2])
            resp.attrs[stun.ATTR_LIFETIME] = struct.pack(">I", 600)
            self.transport.sendto(resp.encode(integrity_key=key), addr)
        elif msg.mtype == stun.CREATE_PERMISSION_REQUEST:
            entry = self.allocs.get(addr)
            peer = msg.xor_address(stun.ATTR_XOR_PEER_ADDRESS)
            ok = entry is not None and peer is not None
            mtype = (stun.CREATE_PERMISSION_SUCCESS if ok
                     else stun.CREATE_PERMISSION_ERROR)
            resp = stun.StunMessage(mtype, txid=msg.txid)
            if ok:
                entry[1].add(peer[0])
            else:
                resp.add_error(437, "Allocation Mismatch")
            self.transport.sendto(resp.encode(), addr)
        elif msg.mtype == stun.REFRESH_REQUEST:
            resp = stun.StunMessage(stun.REFRESH_SUCCESS, txid=msg.txid)
            resp.attrs[stun.ATTR_LIFETIME] = struct.pack(">I", 600)
            self.transport.sendto(resp.encode(), addr)
        elif msg.mtype == stun.SEND_INDICATION:
            entry = self.allocs.get(addr)
            peer = msg.xor_address(stun.ATTR_XOR_PEER_ADDRESS)
            payload = msg.attrs.get(stun.ATTR_DATA)
            if entry is None or peer is None or payload is None:
                return
            relay_tr, perms = entry
            if peer[0] in perms:
                relay_tr.sendto(payload, peer)


class TestAllocationClient:
    def test_allocate_permission_and_data_roundtrip(self):
        async def go():
            mock = MockTurnServer({"alice": "wonder"})
            server_addr = await mock.start()
            got = asyncio.Queue()
            alloc = TurnAllocation(tuple(server_addr), "alice", "wonder",
                                   on_data=lambda d, p: got.put_nowait(
                                       (d, p)))
            relayed = await asyncio.wait_for(alloc.allocate(), 10)
            assert relayed[0] == "127.0.0.1" and relayed[1] > 0

            # a plain UDP peer, reachable only via the relay
            loop = asyncio.get_running_loop()
            peer_q = asyncio.Queue()

            class Peer(asyncio.DatagramProtocol):
                def datagram_received(self, data, addr):
                    peer_q.put_nowait((data, addr))

            peer_tr, _ = await loop.create_datagram_endpoint(
                Peer, local_addr=("127.0.0.1", 0))
            peer_addr = peer_tr.get_extra_info("sockname")

            # without permission the relay must drop both directions
            alloc.send_to(tuple(peer_addr), b"early")
            peer_tr.sendto(b"early-in", tuple(relayed))
            await asyncio.sleep(0.2)
            assert peer_q.empty() and got.empty()

            await alloc.create_permission("127.0.0.1")
            alloc.send_to(tuple(peer_addr), b"hello-out")
            data, src = await asyncio.wait_for(peer_q.get(), 5)
            assert data == b"hello-out"
            assert tuple(src) == tuple(relayed)    # relayed source addr

            peer_tr.sendto(b"hello-in", tuple(relayed))
            data, src = await asyncio.wait_for(got.get(), 5)
            assert data == b"hello-in"
            assert tuple(src) == tuple(peer_addr)

            peer_tr.close()
            alloc.close()
            mock.close()

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 30))

    def test_wrong_password_fails_allocate(self):
        async def go():
            mock = MockTurnServer({"alice": "wonder"})
            server_addr = await mock.start()
            alloc = TurnAllocation(tuple(server_addr), "alice", "WRONG")
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(alloc.allocate(), 10)
            assert mock.auth_failures == 1
            alloc.close()
            mock.close()

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 30))


OFFER_WITH_CANDIDATE = OFFER_TMPL.replace(
    "a=mid:0\r",
    "a=mid:0\r\na=candidate:77 1 udp 2130706431 127.0.0.1 9 typ host\r")


class TestRelayedMediaE2e:
    """The VERDICT 'done' bar: peer reachable ONLY via TURN, SRTP media
    still decodes."""

    @pytest.mark.slow
    def test_relayed_srtp_media_decodes(self, tmp_path):
        cv2 = pytest.importorskip("cv2")

        from docker_nvidia_glx_desktop_tpu.models.h264 import H264Encoder
        from docker_nvidia_glx_desktop_tpu.webrtc.dtls import (
            generate_certificate)
        from docker_nvidia_glx_desktop_tpu.webrtc.peer import WebRtcPeer
        from docker_nvidia_glx_desktop_tpu.webrtc.srtp import SrtpContext

        # encode outside the event loop: one IDR AU for the media check
        enc = H264Encoder(128, 96, qp=26, entropy="device")
        frame = np.zeros((96, 128, 3), np.uint8)
        frame[20:60, 30:90] = (200, 60, 40)
        au = enc.headers() + enc.encode(frame).data

        from docker_nvidia_glx_desktop_tpu.webrtc.dtls import DtlsEndpoint

        async def go():
            mock = MockTurnServer({"srv": "secret"})
            server_addr = await mock.start()
            peer = WebRtcPeer(
                with_audio=False,
                turn={"host": server_addr[0], "port": server_addr[1],
                      "username": "srv", "credential": "secret"})
            cert = generate_certificate("browser")
            b_ufrag = secrets.token_urlsafe(4)
            b_pwd = secrets.token_urlsafe(18)
            answer = await peer.handle_offer(OFFER_WITH_CANDIDATE.format(
                ufrag=b_ufrag, pwd=b_pwd, fp=cert.fingerprint))

            relay_addr = None
            a_ufrag = a_pwd = None
            video_pt = None
            for ln in answer.replace("\r\n", "\n").split("\n"):
                if ln.startswith("m=video"):
                    video_pt = int(ln.rsplit(" ", 1)[1])
                elif ln.startswith("a=ice-ufrag:"):
                    a_ufrag = ln.split(":", 1)[1]
                elif ln.startswith("a=ice-pwd:"):
                    a_pwd = ln.split(":", 1)[1]
                elif ln.startswith("a=candidate:") and " typ relay " in ln:
                    parts = ln.split()
                    relay_addr = (parts[4], int(parts[5]))
            assert relay_addr is not None, "no relay candidate in answer"

            # browser-side UDP socket: talks ONLY to the relayed address
            loop = asyncio.get_running_loop()
            q: asyncio.Queue = asyncio.Queue()

            class Cli(asyncio.DatagramProtocol):
                def datagram_received(self, data, addr):
                    assert tuple(addr) == tuple(relay_addr)
                    q.put_nowait(data)

            tr, _ = await loop.create_datagram_endpoint(
                Cli, local_addr=("127.0.0.1", 0))

            req = stun.StunMessage(stun.BINDING_REQUEST)
            req.add_username(f"{a_ufrag}:{b_ufrag}")
            req.attrs[stun.ATTR_PRIORITY] = struct.pack(">I", 0x7E0000FF)
            req.attrs[stun.ATTR_ICE_CONTROLLING] = secrets.token_bytes(8)
            req.attrs[stun.ATTR_USE_CANDIDATE] = b""
            wire = req.encode(integrity_key=a_pwd.encode())
            for _ in range(5):
                tr.sendto(wire, relay_addr)
                try:
                    data = await asyncio.wait_for(q.get(), 2)
                except asyncio.TimeoutError:
                    continue
                if stun.is_stun(data):
                    resp = stun.StunMessage.decode(data)
                    if resp.mtype == stun.BINDING_SUCCESS:
                        break
            else:
                raise AssertionError("no binding success via relay")
            assert peer.ice.remote_via_relay

            dtls = DtlsEndpoint("client", certificate=cert)
            for d in dtls.start_handshake():
                tr.sendto(d, relay_addr)
            while not dtls.handshake_complete:
                try:
                    data = await asyncio.wait_for(q.get(), 5)
                except asyncio.TimeoutError:
                    for d in dtls.poll_timeout():
                        tr.sendto(d, relay_addr)
                    continue
                if not stun.is_stun(data):
                    for d in dtls.handle_datagram(data):
                        tr.sendto(d, relay_addr)
            _, _, rk, rs = dtls.export_srtp_keys()
            srtp_rx = SrtpContext(rk, rs)
            await asyncio.wait_for(peer.ready, 10)

            for i in range(4):                 # a few sends: loss-free UDP
                peer.send_video_au(au, pts90k=i * 3000)
            dep = rtp.H264Depacketizer()
            aus = []
            deadline = loop.time() + 20
            while not aus and loop.time() < deadline:
                try:
                    data = await asyncio.wait_for(q.get(), 5)
                except asyncio.TimeoutError:
                    continue
                if stun.is_stun(data) or not rtp.is_rtp(data):
                    continue
                if 200 <= data[1] <= 206:
                    continue
                try:
                    plain = srtp_rx.unprotect(data)
                except ValueError:
                    continue
                hdr = rtp.parse_header(plain)
                if hdr["pt"] == video_pt:
                    got = dep.push(hdr["payload"], hdr["marker"])
                    if got is not None:
                        aus.append(got)
            assert aus, "no SRTP video AU arrived via the relay"

            tr.close()
            peer.close()
            mock.close()
            return aus[0]

        au_rx = asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 120))
        # independent decode of the relayed stream
        p = tmp_path / "relay.h264"
        p.write_bytes(au_rx)
        cap = cv2.VideoCapture(str(p))
        ok, img = cap.read()
        cap.release()
        assert ok and img.shape[:2] == (96, 128)


class TestAuthEdgeCases:
    def test_stale_nonce_438_reauth(self):
        """Mid-session nonce rotation: the server answers 438 once; the
        client must re-read realm/nonce and re-sign (RFC 5766 §4)."""
        async def go():
            mock = MockTurnServer({"alice": "wonder"})
            server_addr = await mock.start()
            alloc = TurnAllocation(tuple(server_addr), "alice", "wonder")
            await asyncio.wait_for(alloc.allocate(), 10)

            # rotate the nonce server-side: requests signed with the old
            # nonce now answer 438 with the new one
            orig = mock._on_client
            new_nonce = b"rotated-nonce"
            state = {"rejected": 0}

            async def rotating(data, addr):
                msg = stun.StunMessage.decode(data)
                if (msg.mtype == stun.CREATE_PERMISSION_REQUEST
                        and msg.attrs.get(stun.ATTR_NONCE) != new_nonce):
                    state["rejected"] += 1
                    err = stun.StunMessage(stun.CREATE_PERMISSION_ERROR,
                                           txid=msg.txid)
                    err.add_error(438, "Stale Nonce")
                    err.attrs[stun.ATTR_REALM] = REALM.encode()
                    err.attrs[stun.ATTR_NONCE] = new_nonce
                    mock.transport.sendto(err.encode(), addr)
                    return
                await orig(data, addr)

            mock._on_client = rotating
            await asyncio.wait_for(alloc.create_permission("127.0.0.1"), 10)
            # >= 1: retransmits of the pre-rotation request may also be
            # counted on a slow box; the behavior under test is the
            # nonce update + eventual success, not the reject count
            assert state["rejected"] >= 1
            assert alloc._nonce == new_nonce
            assert "127.0.0.1" in alloc._permissions
            alloc.close()
            mock.close()

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 30))

    def test_no_auth_server(self):
        """A TURN server that grants the first unauthenticated Allocate
        (auth disabled): later requests must stay unauthenticated
        instead of crashing on the missing realm."""
        async def go():
            mock = MockTurnServer({})
            server_addr = await mock.start()

            orig = mock._on_client

            async def no_auth(data, addr):
                msg = stun.StunMessage.decode(data)
                if msg.mtype == stun.ALLOCATE_REQUEST:
                    relay_tr = await mock._make_relay(addr)
                    mock.allocs[addr] = (relay_tr, set())
                    resp = stun.StunMessage(stun.ALLOCATE_SUCCESS,
                                            txid=msg.txid)
                    resp.add_xor_address(
                        stun.ATTR_XOR_RELAYED_ADDRESS,
                        *relay_tr.get_extra_info("sockname")[:2])
                    resp.add_xor_address(stun.ATTR_XOR_MAPPED_ADDRESS,
                                         *addr[:2])
                    resp.attrs[stun.ATTR_LIFETIME] = struct.pack(">I", 600)
                    mock.transport.sendto(resp.encode(), addr)
                    return
                await orig(data, addr)

            mock._on_client = no_auth
            alloc = TurnAllocation(tuple(server_addr), "u", "p")
            relayed = await asyncio.wait_for(alloc.allocate(), 10)
            assert relayed[1] > 0
            await asyncio.wait_for(alloc.create_permission("127.0.0.1"), 10)
            assert "127.0.0.1" in alloc._permissions
            alloc.close()
            mock.close()

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(go(), 30))
