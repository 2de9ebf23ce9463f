"""Proxied client connections (round-4; VERDICT missing #6).

(reference: python/ray/util/client/server/proxier.py — one proxy endpoint,
a dedicated server process per client, version-gated handshake, disconnect
teardown that releases the client's cluster state.)
"""

import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu.util.client.proxier import (_HELLO_MAGIC, PROTOCOL_VERSION,
                                         _recv_json, _send_json, start_proxy)


@pytest.fixture
def cluster_and_proxy():
    import ray_tpu._private.api as _api

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_workers=1)
    gcs_addr = _api._node.address  # host:port TCP control plane
    proxy = start_proxy(gcs_addr)
    yield proxy
    proxy.stop()
    ray_tpu.shutdown()


def test_version_gate(cluster_and_proxy):
    proxy = cluster_and_proxy
    s = socket.create_connection(("127.0.0.1", proxy.port), timeout=10)
    s.sendall(_HELLO_MAGIC)
    _send_json(s, {"client_id": "old", "version": "0.9"})
    reply = _recv_json(s)
    assert reply["ok"] is False
    assert "incompatible" in reply["error"]
    s.close()


def test_bad_magic_dropped(cluster_and_proxy):
    proxy = cluster_and_proxy
    s = socket.create_connection(("127.0.0.1", proxy.port), timeout=10)
    s.sendall(b"GET / HT")  # not a client hello
    s.settimeout(5)
    assert s.recv(64) == b""  # closed without a grant
    s.close()


CLIENT_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import ray_tpu

    ray_tpu.init(address={address!r})

    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(20, 22), timeout=90) == 42
    print("CLIENT_OK", flush=True)
    {tail}
""")


def _run_client(address, tail="ray_tpu.shutdown()", timeout=180):
    code = CLIENT_SCRIPT.format(repo="/root/repo", address=address, tail=tail)
    env = dict(os.environ)
    env.pop("RAY_TPU_SOCKET", None)
    env.pop("RAY_TPU_ADDRESS", None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_proxied_client_runs_tasks(cluster_and_proxy):
    proxy = cluster_and_proxy
    r = _run_client(proxy.address)
    assert "CLIENT_OK" in r.stdout, (r.stdout, r.stderr[-1500:])


@pytest.mark.slow
def test_disconnect_tears_down_client_state(cluster_and_proxy):
    """A client that dies WITHOUT shutdown (hard disconnect) must leave no
    live relay and its driver must be reaped by the GCS."""
    proxy = cluster_and_proxy
    r = _run_client(proxy.address, tail="os._exit(0)  # hard drop")
    assert "CLIENT_OK" in r.stdout, (r.stdout, r.stderr[-1500:])
    deadline = time.time() + 30
    while time.time() < deadline and proxy.num_clients():
        time.sleep(0.2)
    assert proxy.num_clients() == 0  # relay reaped
    # the proxied driver is dead at the GCS (driver-death cleanup ran once
    # the GCS's reader saw the relayed connection close)
    from ray_tpu._private.api import _get_worker

    deadline = time.time() + 20
    while True:
        rows = _get_worker().rpc({"type": "list_workers"})["workers"]
        proxied = [w for w in rows if w.get("kind") == "driver"
                   and w.get("wid") != _get_worker().wid]
        if proxied and all(w["dead"] for w in proxied):
            break
        assert time.time() < deadline, proxied
        time.sleep(0.2)


@pytest.mark.slow
def test_two_clients_isolated_processes(cluster_and_proxy):
    """Each client gets its own relay subprocess (reference: per-client
    SpecificServer)."""
    import threading

    proxy = cluster_and_proxy
    results = {}

    def run(i):
        results[i] = _run_client(
            proxy.address,
            tail=f"import time; time.sleep(2); print('DONE{i}'); "
                 "ray_tpu.shutdown()")

    ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    deadline = time.time() + 120
    peak = 0
    while any(t.is_alive() for t in ts) and time.time() < deadline:
        peak = max(peak, proxy.num_clients())
        time.sleep(0.1)
    for t in ts:
        t.join(timeout=30)
    assert peak >= 2, f"clients shared a relay (peak={peak})"
    for i in (0, 1):
        assert "CLIENT_OK" in results[i].stdout, results[i].stderr[-800:]
