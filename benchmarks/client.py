"""The client side of the served path: one keep-alive HTTP/1.1 connection per
client thread over raw sockets with pre-built request bytes (what wrk or
Gatling do, so the clock measures the server and not ``http.client``), and the
``/metrics`` scrape the counters are read from. Copied from ``bench_e2e.py``'s
``KeepAliveClient`` and ``chip_smoke.py``'s ``metrics`` (PERF.md section 7)."""

import socket
import urllib.parse


def request_bytes(path, params):
    qs = urllib.parse.urlencode(params)
    return (f"GET {path}?{qs} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            "Accept-Encoding: identity\r\n\r\n").encode()


class KeepAliveClient:
    def __init__(self, port, timeout=120):
        self.port, self.timeout = port, timeout
        self.sock, self.buf = None, b""

    def _connect(self):
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def get(self, req):
        """-> (status, body). Reconnects once where the server had closed
        the idle connection."""
        for attempt in (0, 1):
            if self.sock is None:
                self._connect()
            try:
                self.sock.sendall(req)
                return self._read_response()
            except OSError:
                self.close()
                if attempt:
                    raise

    def _read_response(self):
        while b"\r\n\r\n" not in self.buf:
            chunk = self.sock.recv(262144)
            if not chunk:
                raise OSError("connection closed mid-response")
            self.buf += chunk
        head, self.buf = self.buf.split(b"\r\n\r\n", 1)
        lines = head.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        clen = 0
        for ln in lines[1:]:
            k, _, v = ln.partition(b":")
            if k.lower() == b"content-length":
                clen = int(v.strip())
                break
        parts, have = [self.buf], len(self.buf)
        while have < clen:
            chunk = self.sock.recv(262144)
            if not chunk:
                raise OSError("connection closed mid-body")
            parts.append(chunk)
            have += len(chunk)
        data = b"".join(parts)
        body, self.buf = data[:clen], data[clen:]
        return status, body

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock, self.buf = None, b""


METRICS_REQ = request_bytes("/metrics", {})


def parse_metrics(text, keep_labels=()):
    """/metrics text -> {family: summed value}; families named in
    ``keep_labels`` also get ``family{label="v"}`` entries per label set."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, val = line.split(" # ", 1)[0].rpartition(" ")
        try:
            v = float(val)
        except ValueError:
            continue
        fam = name.split("{", 1)[0]
        out[fam] = out.get(fam, 0.0) + v
        if fam in keep_labels:
            out[name] = out.get(name, 0.0) + v
    return out


def scrape_metrics(conn, keep_labels=()):
    status, body = conn.get(METRICS_REQ)
    if status != 200:
        raise RuntimeError(f"/metrics answered {status}")
    return parse_metrics(body.decode(), keep_labels)
