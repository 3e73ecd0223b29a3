"""The rendezvous server with the serving router on it, in a process of its
own, as `hvdrun --serve` keeps it in the launcher.  Never touches jax's
backend.  Prints its port, then serves until stdin closes."""

from __future__ import annotations

import sys

from . import child


def main():
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1", port=int(sys.argv[1]))
    port = server.start()
    child.emit("router", port=port)
    try:
        sys.stdin.read()
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
