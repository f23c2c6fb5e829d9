"""The benchmark's own client and node handling: a minimal RESP2 client,
the child processes a run starts (every node through server_proc.py) and
their end.  Copies of chip_smoke.py's pieces (proven on the chip, PR 21):
the yardstick does not import the smoke, which later PRs may change.
Nothing here imports JAX."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def encode(cmd) -> bytes:
    parts = [p if isinstance(p, bytes) else str(p).encode() for p in cmd]
    return b"*%d\r\n" % len(parts) + b"".join(
        b"$%d\r\n%s\r\n" % (len(p), p) for p in parts)


def reply_end(buf: bytearray, pos: int) -> int:
    """End offset of the complete RESP reply starting at `pos`, or -1."""
    end = buf.find(b"\r\n", pos)
    if end < 0:
        return -1
    t = buf[pos]
    if t in (43, 45, 58):            # + - :
        return end + 2
    n = int(buf[pos + 1:end])
    if t == 36:                      # $
        if n < 0:
            return end + 2
        stop = end + 2 + n + 2
        return stop if stop <= len(buf) else -1
    if t == 42:                      # *
        p = end + 2
        for _ in range(max(n, 0)):
            p = reply_end(buf, p)
            if p < 0:
                return -1
        return p
    raise ValueError(f"unparsable reply byte {bytes(buf[pos:pos + 16])!r}")


class Conn:
    """Pipelines of commands out, parsed replies back."""

    def __init__(self, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.buf = bytearray()
        self.pos = 0

    def close(self) -> None:
        self.sock.close()

    def _fill(self) -> None:
        data = self.sock.recv(1 << 20)
        if not data:
            raise BenchFailure("server closed the connection")
        if self.pos:
            del self.buf[:self.pos]
            self.pos = 0
        self.buf += data

    def _line(self) -> bytes:
        while True:
            end = self.buf.find(b"\r\n", self.pos)
            if end >= 0:
                line = bytes(self.buf[self.pos:end])
                self.pos = end + 2
                return line
            self._fill()

    def _reply(self):
        line = self._line()
        t, rest = line[:1], line[1:]
        if t == b"+":
            return rest.decode()
        if t == b"-":
            raise BenchFailure(f"server error reply: {rest.decode()}")
        if t == b":":
            return int(rest)
        if t == b"$":
            n = int(rest)
            if n < 0:
                return None
            while len(self.buf) - self.pos < n + 2:
                self._fill()
            out = bytes(self.buf[self.pos:self.pos + n])
            self.pos += n + 2
            return out
        if t == b"*":
            n = int(rest)
            return None if n < 0 else [self._reply() for _ in range(n)]
        raise BenchFailure(f"unparsable reply line {line!r}")

    def pipeline(self, cmds: list, depth: int = 64) -> list:
        out = []
        for i in range(0, len(cmds), depth):
            chunk = cmds[i:i + depth]
            self.sock.sendall(b"".join(encode(c) for c in chunk))
            for c in chunk:
                try:
                    out.append(self._reply())
                except BenchFailure as e:
                    raise BenchFailure(f"{c[0]} {c[1:2]!r}: {e}") from None
        return out

    def raw_replies(self, cmds: list) -> list:
        """Send `cmds` as one pipeline; -> each reply's raw bytes."""
        self.sock.sendall(b"".join(encode(c) for c in cmds))
        out = []
        while len(out) < len(cmds):
            end = reply_end(self.buf, self.pos) if self.pos < len(self.buf) \
                else -1
            if end < 0:
                self._fill()
                continue
            out.append(bytes(self.buf[self.pos:end]))
            self.pos = end
        return out

    def cmd(self, *parts):
        return self.pipeline([parts])[0]

    def info(self) -> dict:
        text = self.cmd("info").decode()
        return dict(line.split(":", 1) for line in text.splitlines()
                    if ":" in line and not line.startswith("#"))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Servers:
    """The nodes of one run: booted through server_proc.py, killed at the
    end (SIGKILL: a SIGTERM would write a final dump nobody reads)."""

    def __init__(self, work: str, rehearse: bool):
        self.work = work
        self.rehearse = rehearse
        self.procs = {}

    def sock_path(self, name: str) -> str:
        return os.path.join(self.work, f"{name}.ctl")

    def boot(self, name: str, node: dict, port: int, snapshot: str) -> None:
        """`node`: one entry of the config's `nodes` (engine, node_id,
        `settings` for its TOML file)."""
        engine = node["engine"]
        if self.rehearse:
            engine = "cpu"
        env = dict(os.environ)
        # the program keeps its compile cache where
        # JAX_COMPILATION_CACHE_DIR says, else .jax_cache/ in the checkout
        # (conf.enable_compile_cache); nothing is set here
        if engine == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        argv = [sys.executable, os.path.join(HERE, "server_proc.py"),
                self.sock_path(name)]
        if self.rehearse:
            argv.append("--rehearse")
        argv.append("--")
        settings = node.get("settings") or {}
        if settings:
            toml = os.path.join(self.work, f"{name}.toml")
            with open(toml, "w") as f:
                for k, v in settings.items():
                    f.write(f"{k} = {json.dumps(v)}\n")
            argv.append(toml)
        argv += ["--port", str(port), "--node-id", str(node["node_id"]),
                 "--alias", name, "--engine", engine,
                 "--work-dir", os.path.join(self.work, name),
                 "--snapshot", snapshot, "--log-level", "info"]
        with open(os.path.join(self.work, f"{name}.log"), "ab") as log:
            self.procs[name] = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=log,
                stderr=subprocess.STDOUT)

    def wait_listening(self, name: str, port: int, timeout: float) -> Conn:
        deadline = time.monotonic() + timeout
        while True:
            rc = self.procs[name].poll()
            check(rc is None, f"node {name} exited rc={rc} before it "
                              f"listened:\n{self.log_tail(name)}")
            try:
                return Conn(port)
            except OSError:
                check(time.monotonic() < deadline,
                      f"node {name} not listening after {timeout:.0f}s")
                time.sleep(0.1)

    def control(self, name: str, line: str, timeout: float = 120.0) -> dict:
        """One verb to the node's launcher; an `error` reply raises."""
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(timeout)
            cwd = os.getcwd()     # see server_proc.main: short path
            os.chdir(self.work)
            try:
                s.connect(f"{name}.ctl")
            finally:
                os.chdir(cwd)
            s.sendall(line.encode() + b"\n")
            reply = json.loads(s.makefile("r").readline())
        check("error" not in reply, f"{name} control {line.split()[0]}: "
                                    f"{reply.get('error')}")
        return reply

    def kill_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            p.wait()
        self.procs.clear()

    def log_tail(self, name: str, n: int = 4000) -> str:
        try:
            with open(os.path.join(self.work, f"{name}.log"), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"


def stand_in_trace(trace_dir: str) -> None:
    """A stand-in run has no node to trace: one tiny jitted op in a child
    gives trace_reduce.py its rehearsal input."""
    code = ("import sys, jax, jax.numpy as jnp\n"
            "jax.profiler.start_trace(sys.argv[1])\n"
            "jax.jit(lambda x: (x * 2).sum())(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "jax.profiler.stop_trace()\n")
    subprocess.run([sys.executable, "-c", code, trace_dir], check=True,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
                   capture_output=True)
