"""The system under test, started through its normal entry point
(``dllama_tpu.server.api.main(argv)``) on a thread of the benchmark's own
process, so that this process holds the chip and can trace it.  Flags and HTTP
are the whole interface: no class of the program is imported.
"""

from __future__ import annotations

import gc
import json
import socket
import socketserver
import sys
import threading
import time
import urllib.request


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(base: str, path: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def counter_total(metrics: dict, key: str) -> float:
    """A counter of the /metrics JSON, summed over its labels if it has any."""
    v = metrics.get(key) or 0
    return float(sum(v.values())) if isinstance(v, dict) else float(v)


class Server:
    def __init__(self, argv: list[str]):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.argv = [*argv, "--host", "127.0.0.1", "--port", str(self.port)]
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run, name="dllama-server",
                                       daemon=True)

    def _run(self) -> None:
        try:
            from dllama_tpu.server import api
            api.main(self.argv)
        except BaseException as e:  # noqa: BLE001 — re-raised by wait_ready
            self.error = e
            if not isinstance(e, SystemExit):
                import traceback
                traceback.print_exc(file=sys.stderr)

    def start(self) -> None:
        self.thread.start()

    def wait_ready(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.error is not None or not self.thread.is_alive():
                raise RuntimeError(f"server ended at start-up: {self.error!r}")
            try:
                h = get_json(self.base, "/health", timeout=5)
                if h.get("ready", True):
                    return h
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        raise TimeoutError(f"server not ready within {timeout:.0f} s")

    def metrics(self) -> dict:
        return get_json(self.base, "/metrics")

    def stop(self, timeout: float = 20.0) -> bool:
        """Ask the listening socket server (a stdlib class, found among live
        objects on our port) to shut down, which lets ``main`` run its own
        clean-up; True if the thread ended in time."""
        for obj in gc.get_objects():
            try:
                if isinstance(obj, socketserver.BaseServer) \
                        and obj.server_address[1] == self.port:
                    threading.Thread(target=obj.shutdown, daemon=True).start()
            except Exception:  # noqa: BLE001 — half-built objects in gc's list
                continue
        self.thread.join(timeout)
        return not self.thread.is_alive()
