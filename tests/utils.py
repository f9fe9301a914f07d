"""Shared test helpers: an in-process server harness and a tiny HTTP client."""

import asyncio
import json
from contextlib import asynccontextmanager
from typing import Any, Dict, List, Optional, Tuple


@asynccontextmanager
async def running_server(models: List, **server_kwargs):
    """Start a ModelServer on an ephemeral port for the test body."""
    from kfserving_tpu import ModelServer

    server = ModelServer(http_port=0, **server_kwargs)
    await server.start_async(models, host="127.0.0.1")
    try:
        yield server
    finally:
        await server.stop_async()


async def http_request(port: int, method: str, path: str,
                       body: Optional[bytes] = None,
                       headers: Optional[Dict[str, str]] = None,
                       host: str = "127.0.0.1"
                       ) -> Tuple[int, Dict[str, str], bytes]:
    """Minimal raw HTTP/1.1 client for exercising the server in tests."""
    reader, writer = await asyncio.open_connection(host, port)
    body = body or b""
    head = [f"{method} {path} HTTP/1.1", f"host: {host}:{port}",
            f"content-length: {len(body)}", "connection: close"]
    for k, v in (headers or {}).items():
        head.append(f"{k}: {v}")
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head_raw, _, payload = raw.partition(b"\r\n\r\n")
    lines = head_raw.split(b"\r\n")
    status = int(lines[0].split(b" ")[1])
    resp_headers = {}
    for line in lines[1:]:
        k, _, v = line.decode("latin1").partition(":")
        resp_headers[k.strip().lower()] = v.strip()
    return status, resp_headers, payload


async def http_json(port: int, method: str, path: str,
                    payload: Any = None,
                    headers: Optional[Dict[str, str]] = None
                    ) -> Tuple[int, Any]:
    body = json.dumps(payload).encode() if payload is not None else None
    status, _, raw = await http_request(port, method, path, body, headers)
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def engine_span_lines(trace_dir: str) -> Dict[str, set]:
    """{event name: set of (plane, line) indices that hold it} over the
    `engine.*` events of the one profiler trace under `trace_dir`: which
    thread's line of the trace each engine span landed on."""
    import glob
    import os

    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found: Dict[str, set] = {}
    data = jax.profiler.ProfileData.from_file(path)
    for p, plane in enumerate(data.planes):
        for i, line in enumerate(plane.lines):
            for event in line.events:
                if event.name.startswith("engine."):
                    found.setdefault(event.name, set()).add((p, i))
    return found
