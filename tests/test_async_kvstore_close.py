"""`AsyncKVStore.close()` joins the parameter server it hosts, and the wait
ends: on every worker's ``bye`` at once, and after `_JOIN_S` when a worker
died before it connected or while it was parked in a barrier."""
import socket
import threading
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.parallel import async_kvstore as akv


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _server_threads(server):
    return [server._accept_thread] + server._threads


def test_close_returns_when_a_worker_never_connects(monkeypatch, caplog):
    monkeypatch.setattr(akv, "_JOIN_S", 0.5)
    monkeypatch.setenv("MXNET_TPU_PROCESS_ID", "0")
    monkeypatch.setenv("MXNET_TPU_NUM_PROCESSES", "2")
    monkeypatch.setenv("MXNET_TPU_ASYNC_PORT", str(_free_port()))
    kv = akv.AsyncKVStore()
    server = kv._server
    kv.init(3, mx.nd.array(np.ones((2, 2), "f")))
    closer = threading.Thread(target=kv.close, daemon=True)
    with caplog.at_level("WARNING"):
        closer.start()
        closer.join(20)
    assert not closer.is_alive(), "close() hangs on the absent worker"
    assert not any(t.is_alive() for t in _server_threads(server))
    assert "1 of 2 workers had connected" in caplog.text
    assert kv._server is None and not server._store
    kv.close()                                   # and again: a no-op


def test_join_wakes_a_thread_parked_in_a_barrier():
    port = _free_port()
    server = akv.ParameterServer(2, port, host="127.0.0.1")
    socks = [socket.create_connection(("127.0.0.1", port)) for _ in range(2)]
    akv._send_msg(socks[0], ("barrier",))        # parks: its peer never comes
    deadline = time.monotonic() + 10
    while server._barrier_count != 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert server._barrier_count == 1
    t0 = time.monotonic()
    server.join(timeout=0.3)
    assert time.monotonic() - t0 < 8
    assert not any(t.is_alive() for t in _server_threads(server))
    for s in socks:
        s.close()


def test_join_ends_on_the_last_bye_without_waiting_for_the_deadline():
    port = _free_port()
    server = akv.ParameterServer(2, port, host="127.0.0.1")
    socks = [socket.create_connection(("127.0.0.1", port)) for _ in range(2)]
    joiner = threading.Thread(target=server.join, daemon=True)   # 60 s bound
    joiner.start()
    for s in socks:
        akv._send_msg(s, ("bye",))
        assert akv._recv_msg(s) == ("ok",)
        s.close()
    joiner.join(10)
    assert not joiner.is_alive()
    assert not server._stopping and server._byes == 2
