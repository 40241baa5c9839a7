"""NNG SP Pair0 wire compatibility (``nng+tcp://``).

VERDICT r2 next #5: real NNG peers (the reference demo's fluentd uses
fluent-plugin-nng over libnng, reference: container/Dockerfile_fluentd:5-9)
speak the nanomsg SP TCP mapping — an 8-byte protocol header on connect
(``\\x00SP\\x00`` + proto 16 big-endian + 2 reserved bytes) followed by
``u64_be length | payload`` messages. pynng is not importable in this image,
so interop is pinned at the frame level: a hand-rolled raw socket speaking
exactly the documented wire (what a libnng peer emits) exchanges messages
with the factory's listener and dialer.
"""
import json
import re
import socket
import ssl
import struct
import threading
import time
from pathlib import Path

import pytest
import yaml

from detectmateservice_tpu.engine import (
    Engine,
    NngTcpSocketFactory,
    NngTlsTcpSocketFactory,
)
from detectmateservice_tpu.engine.socket import (
    SP_PAIR0_PROTO,
    TransportError,
    TransportTimeout,
    sp_handshake_bytes,
)
from detectmateservice_tpu.settings import (
    ServiceSettings,
    TlsInputConfig,
    TlsOutputConfig,
)

from conftest import wait_until

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


SP_HEADER = b"\x00SP\x00" + struct.pack("!HH", 16, 0)


def raw_sp_connect(port: int) -> socket.socket:
    """Dial like a libnng Pair0 peer: TCP connect, exchange SP headers."""
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(SP_HEADER)
    got = b""
    while len(got) < 8:
        chunk = s.recv(8 - len(got))
        assert chunk, "listener closed during handshake"
        got += chunk
    assert got == SP_HEADER, got   # symmetric Pair0 header
    return s


def raw_send(s: socket.socket, payload: bytes) -> None:
    s.sendall(struct.pack("!Q", len(payload)) + payload)


def raw_recv(s: socket.socket) -> bytes:
    hdr = b""
    while len(hdr) < 8:
        chunk = s.recv(8 - len(hdr))
        assert chunk, "peer closed"
        hdr += chunk
    (length,) = struct.unpack("!Q", hdr)
    buf = b""
    while len(buf) < length:
        chunk = s.recv(length - len(buf))
        assert chunk, "peer closed mid-message"
        buf += chunk
    return buf


class TestWireFormat:
    def test_handshake_bytes_are_the_documented_sp_header(self):
        # golden: byte-for-byte what a libnng pair0 TCP peer sends
        assert sp_handshake_bytes() == b"\x00\x53\x50\x00\x00\x10\x00\x00"
        assert SP_PAIR0_PROTO == 16

    def test_raw_nng_peer_dials_our_listener(self, free_port):
        listener = NngTcpSocketFactory().create(f"nng+tcp://127.0.0.1:{free_port}")
        listener.recv_timeout = 3000
        peer = raw_sp_connect(free_port)
        raw_send(peer, b"hello from libnng land")
        assert listener.recv() == b"hello from libnng land"
        listener.send(b"reply")          # goes back on the same connection
        assert raw_recv(peer) == b"reply"
        peer.close()
        listener.close()

    def test_our_dialer_reaches_raw_nng_listener(self, free_port):
        """The dialer side speaks the same wire a libnng listener expects."""
        results = {}

        def fake_nng_listener():
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind(("127.0.0.1", free_port))
            srv.listen(1)
            srv.settimeout(5)
            conn, _ = srv.accept()
            conn.sendall(SP_HEADER)
            got = b""
            while len(got) < 8:
                got += conn.recv(8 - len(got))
            results["header"] = got
            results["msg"] = raw_recv(conn)
            raw_send(conn, b"ack")
            conn.close()
            srv.close()

        t = threading.Thread(target=fake_nng_listener)
        t.start()
        dialer = NngTcpSocketFactory().create_output(
            f"nng+tcp://127.0.0.1:{free_port}")
        dialer.recv_timeout = 3000
        # background dial: wait for the connection before the first send
        wait_until(lambda: not _send_raises(dialer, b"payload-1"), timeout=5.0)
        assert dialer.recv() == b"ack"
        t.join()
        assert results["header"] == SP_HEADER
        assert results["msg"] == b"payload-1"
        dialer.close()

    def test_non_sp_peer_rejected(self, free_port):
        listener = NngTcpSocketFactory().create(f"nng+tcp://127.0.0.1:{free_port}")
        listener.recv_timeout = 300
        s = socket.create_connection(("127.0.0.1", free_port), timeout=5)
        s.sendall(b"GET / HTTP/1.1\r\n\r\n")      # not an SP peer
        with pytest.raises(TransportTimeout):
            listener.recv()                        # frame never surfaces
        s.close()
        listener.close()

    def test_wrong_protocol_number_rejected(self, free_port):
        listener = NngTcpSocketFactory().create(f"nng+tcp://127.0.0.1:{free_port}")
        listener.recv_timeout = 300
        s = socket.create_connection(("127.0.0.1", free_port), timeout=5)
        s.sendall(b"\x00SP\x00" + struct.pack("!HH", 0x30, 0))  # req0, not pair0
        time.sleep(0.1)
        with pytest.raises(TransportTimeout):
            listener.recv()
        s.close()
        listener.close()


    def test_closed_listener_refuses_the_next_dial_and_frees_the_port(
            self, free_port):
        """close() has to end the accept, not only mark the listener closed:
        a thread left in accept() keeps the port in LISTEN, so the peer's
        next redial is accepted by a listener that is gone — the peer counts
        frames written that nobody reads (tests/test_chaos.py's churn under
        load) — and a restarted listener cannot bind."""
        addr = f"nng+tcp://127.0.0.1:{free_port}"
        listener = NngTcpSocketFactory().create(addr)
        listener.close()
        with pytest.raises(ConnectionRefusedError):
            socket.create_connection(("127.0.0.1", free_port), timeout=5)
        NngTcpSocketFactory().create(addr).close()     # binds at once


def _send_raises(sock, payload: bytes) -> bool:
    try:
        sock.send(payload, block=False)
        return False
    except Exception:
        return True


def raw_sp_tls_connect(port: int, ca_file: str) -> ssl.SSLSocket:
    """Dial like a libnng tls+tcp Pair0 peer (mbedTLS side): complete the
    TLS handshake FIRST, then exchange the 8-byte SP headers inside the
    session — NNG's layering for its TLS transport."""
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.load_verify_locations(ca_file)
    raw = socket.create_connection(("127.0.0.1", port), timeout=5)
    s = ctx.wrap_socket(raw, server_hostname="localhost")
    s.sendall(SP_HEADER)
    got = b""
    while len(got) < 8:
        chunk = s.recv(8 - len(got))
        assert chunk, "listener closed during handshake"
        got += chunk
    assert got == SP_HEADER, got
    return s


class TestNngTlsWire:
    """nng+tls+tcp://: the SP Pair0 wire inside a real TLS session —
    byte-compatible with NNG's ``tls+tcp`` transport (mbedTLS under libnng),
    the reference's encrypted interop mode (reference:
    src/service/features/engine_socket.py:60-71, engine.py:165-170).
    VERDICT r4 next #3."""

    def test_raw_tls_nng_peer_dials_our_listener(self, tls_material, free_port):
        listener = NngTlsTcpSocketFactory().create(
            f"nng+tls+tcp://127.0.0.1:{free_port}",
            tls_config=TlsInputConfig(cert_key_file=tls_material["cert_key_file"]))
        listener.recv_timeout = 5000
        peer = raw_sp_tls_connect(free_port, tls_material["ca_file"])
        raw_send(peer, b"encrypted hello")
        assert listener.recv() == b"encrypted hello"
        listener.send(b"encrypted reply")
        assert raw_recv(peer) == b"encrypted reply"
        peer.close()
        listener.close()

    def test_our_dialer_reaches_raw_tls_nng_listener(self, tls_material, free_port):
        """Dialer side: TLS client handshake, then SP inside the session —
        what an mbedTLS NNG listener (e.g. a TLS-configured fluentd edge)
        expects on accept."""
        results = {}

        def fake_tls_nng_listener():
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls_material["cert_key_file"])
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind(("127.0.0.1", free_port))
            srv.listen(1)
            srv.settimeout(10)
            raw, _ = srv.accept()
            conn = ctx.wrap_socket(raw, server_side=True)
            conn.sendall(SP_HEADER)
            got = b""
            while len(got) < 8:
                got += conn.recv(8 - len(got))
            results["header"] = got
            results["msg"] = raw_recv(conn)
            raw_send(conn, b"ack over tls")
            conn.close()
            srv.close()

        t = threading.Thread(target=fake_tls_nng_listener)
        t.start()
        dialer = NngTlsTcpSocketFactory().create_output(
            f"nng+tls+tcp://127.0.0.1:{free_port}",
            tls_config=TlsOutputConfig(ca_file=tls_material["ca_file"],
                                       server_name="localhost"))
        dialer.recv_timeout = 5000
        wait_until(lambda: not _send_raises(dialer, b"tls-payload-1"), timeout=10.0)
        assert dialer.recv() == b"ack over tls"
        t.join()
        assert results["header"] == SP_HEADER
        assert results["msg"] == b"tls-payload-1"
        dialer.close()

    def test_plaintext_sp_peer_rejected_by_tls_listener(self, tls_material, free_port):
        """An UNencrypted SP peer must not get through a TLS listener — its
        first bytes are not a ClientHello, so the handshake fails and no
        frame ever surfaces."""
        listener = NngTlsTcpSocketFactory().create(
            f"nng+tls+tcp://127.0.0.1:{free_port}",
            tls_config=TlsInputConfig(cert_key_file=tls_material["cert_key_file"]))
        listener.recv_timeout = 300
        s = socket.create_connection(("127.0.0.1", free_port), timeout=5)
        s.sendall(SP_HEADER + struct.pack("!Q", 5) + b"plain")
        with pytest.raises(TransportTimeout):
            listener.recv()
        s.close()
        listener.close()

    def test_listener_requires_cert_before_listen(self, free_port):
        """TLS material is validated BEFORE the socket binds (the ordering
        contract, reference: tests/test_tls_transport.py:156-188) — and the
        port stays free afterwards."""
        with pytest.raises(TransportError):
            NngTlsTcpSocketFactory().create(
                f"nng+tls+tcp://127.0.0.1:{free_port}", tls_config=None)
        with pytest.raises(TransportError):
            NngTlsTcpSocketFactory().create(
                f"nng+tls+tcp://127.0.0.1:{free_port}",
                tls_config=TlsInputConfig(cert_key_file="/nonexistent.pem"))
        # bind never happened: the port is still available
        probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        probe.bind(("127.0.0.1", free_port))
        probe.close()

    def test_dialer_requires_ca(self, free_port):
        with pytest.raises(TransportError):
            NngTlsTcpSocketFactory().create_output(
                f"nng+tls+tcp://127.0.0.1:{free_port}", tls_config=None)

    def test_settings_require_tls_material_for_scheme(self, free_port):
        with pytest.raises(Exception, match="tls_input"):
            ServiceSettings(component_type="core",
                            engine_addr=f"nng+tls+tcp://127.0.0.1:{free_port}",
                            log_to_file=False)
        with pytest.raises(Exception, match="tls_output"):
            ServiceSettings(component_type="core",
                            out_addr=[f"nng+tls+tcp://127.0.0.1:{free_port}"],
                            log_to_file=False)

    def test_engine_output_dials_tls_listener(self, tls_material, free_port):
        """The ENGINE forwards tls_output to the factory for nng+tls+tcp
        out addrs. Integration gap the factory-level tests missed: settings
        validation guaranteed the material existed, but the engine's output
        setup only forwarded it for tls+tcp:// — every encrypted NNG output
        failed at dial with 'requires tls_output.ca_file'."""
        from detectmateservice_tpu.engine.socket import ZmqPairSocketFactory

        listener = NngTlsTcpSocketFactory().create(
            f"nng+tls+tcp://127.0.0.1:{free_port}",
            tls_config=TlsInputConfig(cert_key_file=tls_material["cert_key_file"]))
        listener.recv_timeout = 8000
        settings = ServiceSettings(
            component_type="core",
            engine_addr="inproc://tls-out-engine",
            out_addr=[f"nng+tls+tcp://127.0.0.1:{free_port}"],
            tls_output=TlsOutputConfig(ca_file=tls_material["ca_file"],
                                       server_name="localhost"),
            log_to_file=False,
        )

        class Upper:
            def process(self, data: bytes):
                return data.upper()

        engine = Engine(settings, Upper(), ZmqPairSocketFactory())
        engine.start()
        ingress = ZmqPairSocketFactory().create_output("inproc://tls-out-engine")
        # pump until one delivery lands: the engine's bounded send-retry may
        # drop the first messages while the background TLS dial completes
        done = threading.Event()

        def pump():
            while not done.is_set():
                try:
                    ingress.send(b"encrypted out", block=False)
                except TransportError:
                    pass
                time.sleep(0.05)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        try:
            assert listener.recv() == b"ENCRYPTED OUT"
        finally:
            done.set()
            t.join()
        ingress.close()
        engine.stop()
        listener.close()

    def test_engine_serves_raw_tls_nng_peer(self, tls_material, free_port):
        """Full stack parity with TestEngineOverNngTcp, encrypted: an Engine
        on nng+tls+tcp:// echoes to a raw TLS+SP peer."""
        settings = ServiceSettings(
            component_type="core",
            engine_addr=f"nng+tls+tcp://127.0.0.1:{free_port}",
            tls_input=TlsInputConfig(cert_key_file=tls_material["cert_key_file"]),
            log_to_file=False,
        )

        class Rev:
            def process(self, data: bytes):
                return data[::-1]

        engine = Engine(settings, Rev(), NngTlsTcpSocketFactory())
        engine.start()
        peer = raw_sp_tls_connect(free_port, tls_material["ca_file"])
        raw_send(peer, b"abcdef")
        assert raw_recv(peer) == b"fedcba"
        peer.close()
        engine.stop()


class TestEngineOverNngTcp:
    def test_engine_serves_raw_nng_peer(self, free_port):
        """Full stack: a reference-style raw SP peer sends to an Engine
        listening on nng+tcp://; the processed reply comes back on the same
        Pair0 connection (no-outputs echo contract)."""
        settings = ServiceSettings(
            component_type="core",
            engine_addr=f"nng+tcp://127.0.0.1:{free_port}",
            log_to_file=False,
        )

        class Rev:
            def process(self, data: bytes):
                return data[::-1]

        engine = Engine(settings, Rev(), NngTcpSocketFactory())
        engine.start()
        peer = raw_sp_connect(free_port)
        raw_send(peer, b"abcdef")
        assert raw_recv(peer) == b"fedcba"
        peer.close()
        engine.stop()


# ---------------------------------------------------------------------------
# Fluentd payload contract (VERDICT r4 next #4): pin the exact payloads the
# committed confs make the stock fluentd edge emit/consume, end to end.
# ---------------------------------------------------------------------------

REPO_ROOT = Path(__file__).resolve().parent.parent


def fluentd_json_payload(line: str, path: str, hostname: str) -> bytes:
    """Byte shape of one message from the INGRESS edge as committed:
    ``container/fluentin/fluent.conf`` tails with ``<parse> @type none``
    (record = {"message": line}), adds ``path_key logSource`` and
    ``<inject> hostname_key hostname``, and formats with ``<format> @type
    json`` — fluentd's json formatter emits ``record.to_json + "\\n"``."""
    return (json.dumps({"message": line, "logSource": path,
                        "hostname": hostname}) + "\n").encode()


class TestFluentdPayloadContract:
    def test_decode_maps_json_record_onto_logschema(self):
        """message→log, logSource→logSource, hostname→hostname — the same
        mapping the reference's fluent-plugin-detectmate formatter performs
        (reference: container/fluentin/fluent.conf:155-166)."""
        from detectmateservice_tpu.library.parsers.template_matcher import (
            decode_ingest_payload,
        )

        line = 'type=SYSCALL msg=audit(1700000000.123): pid=421 comm="cron"'
        msg = decode_ingest_payload(
            fluentd_json_payload(line, "/fluentd/log/audit.log", "edge-7"), True)
        assert msg.log == line
        assert msg.logSource == "/fluentd/log/audit.log"
        assert msg.hostname == "edge-7"
        assert msg.logID == ""

    def test_decode_accepts_single_value_bare_line(self):
        """`<format> @type single_value` emits the bare line + "\\n"
        (add_newline default): exactly one trailing newline is stripped,
        interior whitespace preserved."""
        from detectmateservice_tpu.library.parsers.template_matcher import (
            decode_ingest_payload,
        )

        msg = decode_ingest_payload(b"type=LOGIN msg=audit(1.2):  x\n", True)
        assert msg.log == "type=LOGIN msg=audit(1.2):  x"
        assert msg.logSource == "" and msg.hostname == ""

    def test_decode_prefers_logschema_envelope(self):
        """A genuine LogSchema protobuf (the reference formatter's output)
        always wins over the raw interpretations."""
        from detectmateservice_tpu.library.parsers.template_matcher import (
            decode_ingest_payload,
        )
        from detectmateservice_tpu.schemas import LogSchema

        payload = LogSchema(logID="id-1", log="the line",
                            logSource="/var/log/x", hostname="h").serialize()
        msg = decode_ingest_payload(payload, True)
        assert (msg.logID, msg.log, msg.logSource, msg.hostname) == (
            "id-1", "the line", "/var/log/x", "h")

    def test_strict_mode_rejects_raw_payloads(self):
        """accept_raw_lines=false keeps the reference's strict contract:
        non-protobuf payloads raise (pinned error taxonomy)."""
        from detectmateservice_tpu.library.common.core import LibraryError
        from detectmateservice_tpu.library.parsers.template_matcher import (
            MatcherParser,
        )

        parser = MatcherParser(config={"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "params": {"accept_raw_lines": False}}}})
        with pytest.raises(LibraryError, match="LogSchema"):
            parser.process(b"\xff\xfe not a protobuf nor a line\xff")

    def test_ingress_edge_end_to_end(self, run_service, tmp_path, free_port):
        """Full committed-conf pipeline shape: a raw SP Pair0 peer (the role
        fluent-plugin-nng plays, dialing ``tcp://parser:5801``) sends the
        exact json-formatter payloads into a real MatcherParser service
        listening on nng+tcp://, configured like container/config/
        parser_config.yaml (accept_raw_lines: true); the ParserSchema
        output arrives at a raw SP listener standing in for the detector."""
        from detectmateservice_tpu.core import Service

        parser_config = tmp_path / "parser_config.yaml"
        parser_config.write_text(yaml.safe_dump({"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": "type=<Type> msg=audit(<Time>): <Content>",
            "time_format": None,
            "params": {"remove_spaces": False, "remove_punctuation": False,
                       "lowercase": False, "path_templates": None,
                       "accept_raw_lines": True},
        }}}))
        out_port = free_port
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.bind(("127.0.0.1", 0))
            in_port = s.getsockname()[1]

        downstream = NngTcpSocketFactory().create(f"nng+tcp://127.0.0.1:{out_port}")
        downstream.recv_timeout = 8000
        settings = ServiceSettings(
            component_type="parsers.template_matcher.MatcherParser",
            engine_addr=f"nng+tcp://127.0.0.1:{in_port}",
            out_addr=[f"nng+tcp://127.0.0.1:{out_port}"],
            config_file=str(parser_config),
            http_host="127.0.0.1", http_port=0, log_to_file=False,
        )
        run_service(Service(settings, socket_factory=NngTcpSocketFactory()))

        edge = raw_sp_connect(in_port)
        line = 'type=SYSCALL msg=audit(1700000000.101): pid=421 uid=0 comm="cron"'
        raw_send(edge, fluentd_json_payload(line, "/fluentd/log/audit.log", "edge-7"))

        from detectmateservice_tpu.schemas import ParserSchema

        parsed = ParserSchema.from_bytes(downstream.recv())
        assert parsed.get("logFormatVariables") == {
            "Type": "SYSCALL", "Time": "1700000000.101",
            "Content": 'pid=421 uid=0 comm="cron"'}
        assert parsed.get("parserType") == "matcher_parser"
        # reference quirk preserved: `log` carries the parser name
        assert parsed.get("log") == parsed.get("parserID")

        # the single_value alternative documented in the conf works too
        raw_send(edge, b'type=LOGIN msg=audit(1700000000.222): pid=9 uid=1\n')
        parsed2 = ParserSchema.from_bytes(downstream.recv())
        assert parsed2.get("logFormatVariables") == {
            "Type": "LOGIN", "Time": "1700000000.222", "Content": "pid=9 uid=1"}
        edge.close()
        downstream.close()

    def test_egress_edge_decodes_detector_schema(self, free_port):
        """EGRESS contract: what the framework's out_addr sends over
        nng+tcp:// must decode as the DetectorSchema that
        container/fluentout/fluent.conf's protobuf parser (class_file
        schemas_pb.rb, class_name DetectorSchema) expects."""
        from detectmateservice_tpu.schemas import DetectorSchema, schemas_pb2

        fluentout = NngTcpSocketFactory().create(f"nng+tcp://127.0.0.1:{free_port}")
        fluentout.recv_timeout = 8000
        settings = ServiceSettings(
            component_type="core",
            engine_addr="inproc://egress-test",
            out_addr=[f"nng+tcp://127.0.0.1:{free_port}"],
            log_to_file=False,
        )

        class Passthrough:
            def process(self, data: bytes):
                return data

        from detectmateservice_tpu.engine.socket import ZmqPairSocketFactory

        engine = Engine(settings, Passthrough(), ZmqPairSocketFactory())
        engine.start()
        alert = DetectorSchema(
            detectorID="det-1", detectorType="new_value_detector",
            alertID="a-1", detectionTimestamp=1700000000,
            logIDs=["41", "42"], score=0.75,
            description="unknown value", alertsObtain={"k": "v"},
        ).serialize()
        ingress = ZmqPairSocketFactory().create_output("inproc://egress-test")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            try:
                ingress.send(alert, block=False)
                break
            except TransportError:
                time.sleep(0.05)
        wire = fluentout.recv()
        decoded = schemas_pb2.DetectorSchema()
        decoded.ParseFromString(wire)
        assert decoded.detectorID == "det-1"
        assert list(decoded.logIDs) == ["41", "42"]
        assert decoded.score == pytest.approx(0.75)
        assert dict(decoded.alertsObtain) == {"k": "v"}
        ingress.close()
        engine.stop()
        fluentout.close()

    def test_schemas_pb_rb_matches_python_descriptors(self):
        """The committed Ruby descriptor (container/fluentout/schemas_pb.rb,
        what fluent-plugin-parser-protobuf loads) must agree field-by-field
        — name, type, number, label — with the schemas_pb2 the Python side
        serializes with. A drifted field number would silently decode wrong
        values at the egress edge (score is field 8: reference
        container/fluentout/schemas_pb.rb:8)."""
        from google.protobuf import descriptor as _d

        from detectmateservice_tpu.schemas import _is_repeated, schemas_pb2

        rb_text = (REPO_ROOT / "container" / "fluentout" / "schemas_pb.rb").read_text()
        rb: dict = {}
        current = None
        for raw_line in rb_text.splitlines():
            line = raw_line.strip()
            m = re.match(r'add_message "(\w+)" do', line)
            if m:
                current = rb.setdefault(m.group(1), {})
                continue
            m = re.match(r"(optional|proto3_optional|repeated)\s+:(\w+),\s+:(\w+),\s+(\d+)", line)
            if m and current is not None:
                kind = "repeated" if m.group(1) == "repeated" else "singular"
                current[m.group(2)] = (kind, m.group(3), int(m.group(4)))
                continue
            m = re.match(r"map\s+:(\w+),\s+:(\w+),\s+:(\w+),\s+(\d+)", line)
            if m and current is not None:
                current[m.group(1)] = ("map", f"{m.group(2)}->{m.group(3)}",
                                       int(m.group(4)))
        assert set(rb) >= {"Schema", "LogSchema", "ParserSchema",
                           "DetectorSchema", "OutputSchema"}

        type_names = {_d.FieldDescriptor.TYPE_STRING: "string",
                      _d.FieldDescriptor.TYPE_INT32: "int32",
                      _d.FieldDescriptor.TYPE_FLOAT: "float"}
        for msg_name, rb_fields in rb.items():
            py_msg = getattr(schemas_pb2, msg_name).DESCRIPTOR
            py_fields = {}
            for f in py_msg.fields:
                if (_is_repeated(f)
                        and f.message_type is not None
                        and f.message_type.GetOptions().map_entry):
                    entry = f.message_type.fields_by_name
                    py_fields[f.name] = (
                        "map",
                        f"{type_names[entry['key'].type]}->{type_names[entry['value'].type]}",
                        f.number)
                elif _is_repeated(f):
                    py_fields[f.name] = ("repeated", type_names[f.type], f.number)
                else:
                    py_fields[f.name] = ("singular", type_names[f.type], f.number)
            assert rb_fields == py_fields, f"descriptor drift in {msg_name}"
