"""Loop kind ``closed``: a fixed number of connections (the mix's
``connections``), each sending its next request when the reply to the last
has arrived.  One thread, one selector, raw HTTP/1.1 over persistent
sockets, so the generator's own cost per request is a few tens of
microseconds and no interpreter lock is shared between connections.

A loop kind is a module of this directory that run.py finds by the mix's
``loop``: ``Loop(port, mix)`` with ``run``, ``close``, ``conns`` and
``turnaround`` as below."""

import selectors
import socket
import time

REPLY_GRACE_S = 60.0  # a reply may come this long after the window closes


class Exchange:
    """One HTTP request of the run and what came back."""

    __slots__ = ("request", "conn", "t_send", "t_done", "status", "body")

    def __init__(self, request, conn, t_send):
        self.request = request
        self.conn = conn
        self.t_send = t_send
        self.t_done = None  # stays None if the reply never came
        self.status = None
        self.body = b""


class _Conn:
    __slots__ = ("sock", "buf", "need", "head_len", "current", "last_done")

    def __init__(self, sock):
        self.sock = sock
        self.buf = bytearray()
        self.need = None  # total reply bytes once the head is parsed
        self.head_len = 0
        self.current = None
        self.last_done = None


def http_post(path: str, body: bytes) -> bytes:
    return (f"POST {path} HTTP/1.1\r\nHost: bench\r\nContent-Length: {len(body)}\r\n\r\n"
            .encode() + body)


class Loop:
    def __init__(self, port: int, mix: dict):
        self.conns = []
        for _ in range(mix["connections"]):
            s = socket.create_connection(("127.0.0.1", port))
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.setblocking(False)
            self.conns.append(_Conn(s))
        self.turnaround = []  # reply -> next send, seconds, every connection

    def close(self):
        for c in self.conns:
            c.sock.close()

    def _send(self, ci: int, request, out: list):
        c = self.conns[ci]
        data = request.wire
        t = time.perf_counter()
        if c.last_done is not None:
            self.turnaround.append(t - c.last_done)
        ex = Exchange(request, ci, t)
        c.current = ex
        out.append(ex)
        view = memoryview(data)
        while view:
            try:
                n = c.sock.send(view)
            except BlockingIOError:
                time.sleep(0.0002)  # loopback buffer full: cannot last
                continue
            view = view[n:]

    def _on_readable(self, c: _Conn) -> bool:
        """Feed the connection; True once its reply is complete."""
        try:
            chunk = c.sock.recv(1 << 18)
        except BlockingIOError:
            return False
        if not chunk:
            raise ConnectionError("server closed a connection mid-run")
        c.buf += chunk
        if c.need is None:
            end = c.buf.find(b"\r\n\r\n")
            if end < 0:
                return False
            head = bytes(c.buf[:end]).decode("latin-1")
            lines = head.split("\r\n")
            c.current.status = int(lines[0].split(" ", 2)[1])
            clen = None
            for ln in lines[1:]:
                k, _, v = ln.partition(":")
                if k.lower() == "content-length":
                    clen = int(v)
            if clen is None:
                raise ConnectionError(f"reply without Content-Length: {head[:200]!r}")
            c.head_len = end + 4
            c.need = c.head_len + clen
        if len(c.buf) < c.need:
            return False
        c.current.t_done = time.perf_counter()
        c.current.body = bytes(c.buf[c.head_len:c.need])
        del c.buf[:c.need]
        c.need = None
        c.last_done = c.current.t_done
        c.current = None
        return True

    def run(self, seconds: float, next_request, width: int = None) -> tuple:
        """Drive the loop for ``seconds`` on the first ``width``
        connections (default: all); returns (exchanges, t_start, t_close).
        No request is sent after t_close; replies still outstanding then
        are waited for REPLY_GRACE_S.  ``seconds`` 0 is one burst."""
        conns = self.conns[:width]
        sel = selectors.DefaultSelector()
        for i, c in enumerate(conns):
            c.last_done = None
            sel.register(c.sock, selectors.EVENT_READ, i)
        out = []
        t_start = time.perf_counter()
        t_close = t_start + seconds
        for i in range(len(conns)):
            self._send(i, next_request(), out)
        busy = len(conns)
        try:
            while busy:
                now = time.perf_counter()
                if now > t_close + REPLY_GRACE_S:
                    break  # what is still out never came
                for key, _ in sel.select(timeout=0.05):
                    c = self.conns[key.data]
                    if c.current is None or not self._on_readable(c):
                        continue
                    if time.perf_counter() < t_close:
                        self._send(key.data, next_request(), out)
                    else:
                        busy -= 1
        finally:
            sel.close()
        return out, t_start, t_close
