"""Store high availability end to end: primary + network standby,
kill -9, automatic election, client failover — the mongo replica-set
story (reference docker-compose.yml:42-90) with first-party processes.

Runs on CPU out of the box::

    JAX_PLATFORMS=cpu python examples/ha_failover_demo.py

Flow:

1. a PRIMARY api server (its own store directory) and a STANDBY
   (its own directory on what would be another machine — WALs ship
   over the primary's ``/replication`` HTTP routes, no shared disk);
2. the client writes artifacts through the primary, with
   ``failover=`` pointing at the standby;
3. ``kill -9`` the primary mid-flight: the standby detects the dead
   health probe, promotes itself (election epoch 1), and serves the
   full REST API on its own port;
4. the SAME client object keeps working — reads see every
   acknowledged write, new writes land on the promoted standby.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time

try:  # repo on sys.path, for both invocation styles
    import _demo_env  # noqa: F401  (python examples/<name>.py)
except ImportError:
    from examples import _demo_env  # noqa: F401  (python -m examples.<name>)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_health(ctx, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            ctx.request("GET", "/health")
            return
        except Exception:
            time.sleep(0.3)
    raise RuntimeError("server never became healthy")


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="lo_ha_demo_")
    api_port, standby_port = _free_port(), _free_port()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({
        # Two server processes on one host cannot share a chip (one
        # process owns it); the demo is about the STORE plane, so both
        # children are pinned to the CPU.
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "LO_TPU_API_PORT": str(api_port),
        "LO_TPU_STORE_ROOT": f"{workdir}/primary/store",
        "LO_TPU_VOLUME_ROOT": f"{workdir}/primary/volumes",
        # The arming wait below reads the standby's INFO log line.
        "LO_TPU_LOG_LEVEL": "INFO",
    })

    from learningorchestra_tpu.client import Context

    procs = []
    try:
        # 1. Primary + network standby (independent directories) ----------
        primary = subprocess.Popen(
            [sys.executable, "-m", "learningorchestra_tpu", "serve",
             "--port", str(api_port)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        procs.append(primary)
        ctx = Context(f"http://127.0.0.1:{api_port}",
                      failover=f"127.0.0.1:{standby_port}")
        _wait_health(ctx)

        standby = subprocess.Popen(
            [sys.executable, "-m", "learningorchestra_tpu", "standby",
             "--primary", f"127.0.0.1:{api_port}",
             "--replica", f"{workdir}/standby/store",
             "--port", str(standby_port), "--host", "127.0.0.1",
             "--interval", "0.3", "--misses", "4"],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        procs.append(standby)
        print(f"primary :{api_port}  standby :{standby_port} "
              f"(WALs over HTTP, no shared disk)")

        # Takeover requires FIRST CONTACT (a cold-booted standby must
        # never fence a primary it has never seen), and the standby
        # pays ~10 s of imports before its first probe — wait for the
        # arming line before any failure is induced.  select()-based:
        # a blocked readline would defeat the deadline, and EOF (a
        # crashed standby) must raise, not fall through.
        import select

        deadline = time.time() + 60
        armed, tail = False, ""
        while time.time() < deadline and not armed:
            ready, _, _ = select.select([standby.stdout], [], [], 0.5)
            if not ready:
                continue
            line = standby.stdout.readline()
            if not line:  # EOF: the standby died during startup
                break
            tail = (tail + line)[-2000:]
            armed = "takeover arming enabled" in line
        if not armed:
            raise RuntimeError(
                f"standby never armed; last output:\n{tail}"
            )
        print("standby armed (first contact made)")

        # 2. Acknowledged writes through the primary ----------------------
        for i in range(5):
            ctx.function.create(f"gen1_{i}",
                                function=f"response = {i} * {i}")
        for i in range(5):
            ctx.function.wait(f"gen1_{i}")
        print("5 artifacts written and finished on the primary")
        time.sleep(1.5)  # > one shipping interval: let the tail ship

        # 3. Murder the primary ------------------------------------------
        primary.send_signal(signal.SIGKILL)
        primary.wait(timeout=10)
        print("primary killed (SIGKILL) — standing by for election…")

        # 4. Same client, no reconfiguration ------------------------------
        deadline = time.time() + 90
        docs = None
        while time.time() < deadline:
            try:
                docs = ctx.function.search("gen1_0")
                break
            except Exception:
                time.sleep(0.5)
        assert docs and docs[0]["name"] == "gen1_0", docs
        for i in range(5):
            docs = ctx.function.search(f"gen1_{i}")
            assert docs and docs[0].get("finished"), (i, docs)
        print("every acknowledged write readable after failover")

        ctx.function.create(
            "gen2", function="response = 'written-after-failover'"
        )
        meta = ctx.function.wait("gen2")
        assert meta.get("finished"), meta
        print("new write accepted by the promoted standby — "
              "failover complete (election epoch 1)")
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass  # kill all first; never orphan the rest


if __name__ == "__main__":
    main()
