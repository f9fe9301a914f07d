"""Server children and the client's view of them.

The benchmark's own process never imports JAX: a chip belongs to one process
at a time, so the server under test runs as a child (`python -m <module>`),
owns the chip, and is read from outside only: HTTP, /metrics, its log.
(The shape of this file follows chip_smoke.py, which proved it on the chip
in PR 21; nothing is imported from there.)
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Everything a run writes: model dirs, logs, seeded parameters, reference
# answers, traces.  Git-ignored (.kfs_cache/), inside the checkout, fixed.
WORK = os.path.join(ROOT, ".kfs_cache", "chipbench")
READY_TIMEOUT_S = 1000.0


class BenchFailure(Exception):
    """The run cannot give a result: non-zero exit, no result line."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def child_env(config_name: str, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = (ROOT + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    # Seeded parameters are materialised once per configuration and mapped
    # by every later child, the CPU reference included: the same bytes.
    env["KFS_PARAM_CACHE"] = param_cache_dir(config_name)
    # JAX's compile cache: one fixed directory inside the checkout, with no
    # size limit, whatever the machine's environment says.  The path is part
    # of the cache's key, two checkouts must share nothing, and a limit
    # smaller than a cell's programs makes every run compile again (the chip
    # tool's 192 MiB did: PERF.md, PR 23).
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".kfs_cache", "xla")
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    env.update(extra)
    return env


def param_cache_dir(config_name: str) -> str:
    return os.path.join(WORK, "params", config_name)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: bytes = None, headers: dict = None,
         timeout_s: float = 600.0) -> bytes:
    """The response body; urllib raises on any non-2xx status."""
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return resp.read()


def post_json(url: str, payload: dict, timeout_s: float = 600.0) -> dict:
    return json.loads(http("POST", url, json.dumps(payload).encode(),
                           {"content-type": "application/json"}, timeout_s))


def stop(proc: subprocess.Popen) -> None:
    """SIGTERM, then SIGKILL; returns when the process has ended."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


class Server:
    """One chip-owning server child on a free port, its output in a log
    file.  JAX_LOG_COMPILES makes JAX itself write one line per program it
    traces and compiles (or loads from its cache), which is how a compile
    inside the window is seen from outside."""

    def __init__(self, module: str, name: str, model_config: dict,
                 config_name: str):
        self.name = name
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        model_dir = os.path.join(WORK, "models", name)
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(model_config, f)
        self.log_path = os.path.join(WORK, "logs", f"{name}.log")
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self._log = open(self.log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module, "--model_name", name,
             "--model_dir", model_dir, "--http_port", str(self.port)],
            cwd=ROOT, env=child_env(config_name, JAX_LOG_COMPILES="1"),
            stdout=self._log, stderr=subprocess.STDOUT)

    def log_text(self, start: int = 0, end: int = None) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(start)
            data = f.read() if end is None else f.read(max(0, end - start))
        return data.decode(errors="replace")

    def _wait(self, what: str, probe, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"{self.name} exited {self.proc.returncode} before "
                    f"{what}:\n{self.log_text()[-4000:]}")
            found = probe()
            if found is not None:
                return found
            time.sleep(0.1)
        raise BenchFailure(f"{self.name}: no {what} within {timeout_s:.0f}s:"
                           f"\n{self.log_text()[-4000:]}")

    def device(self, platform: str, chips: int) -> dict:
        """The device record the child logs before its model loads.  Another
        platform than `platform`, or fewer chips than the cell asks for,
        ends the run here, in seconds."""
        def probe():
            for line in self.log_text().splitlines():
                _, mark, record = line.partition(
                    "kfserving_tpu.startup:device ")
                if mark:
                    return json.loads(record)
            return None

        device = self._wait("device report", probe, 180.0)
        if device["platform"] != platform or device["count"] < chips:
            raise BenchFailure(
                f"{self.name} holds {device['count']} x "
                f"{device['platform']!r}; the cell needs {chips} x "
                f"{platform!r}")
        return device

    def wait_ready(self) -> float:
        """Seconds from spawn until the health route answers ready."""
        def probe():
            try:
                body = http("GET", f"{self.base}/v1/models/{self.name}",
                            timeout_s=5.0)
            except (OSError, urllib.error.URLError):
                return None
            return True if json.loads(body).get("ready") else None

        self._wait("ready", probe, READY_TIMEOUT_S)
        return time.monotonic() - self.started

    def get_json(self, path: str) -> dict:
        return json.loads(http("GET", self.base + path))

    def close(self) -> None:
        stop(self.proc)
        self._log.close()


_COMPILING = re.compile(
    r"^WARNING:(\d{4}-)?[^\n]*?pxla(?::\d+)?: ?Compiling ([^\n]*)$",
    re.MULTILINE)


def compile_lines(log_text: str) -> list:
    """What follows "Compiling " on each of JAX's log_compiles lines: the
    program's name and its argument shapes.  JAX's own handler stamps the
    line with the time and the servers' root logger repeats it without:
    one form is counted, the stamped one where there is any."""
    found = _COMPILING.findall(log_text)
    stamped = [rest for stamp, rest in found if stamp]
    return stamped or [rest for _, rest in found]
