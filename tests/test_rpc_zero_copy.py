"""Zero-copy RPC tensor framing (VERDICT r2 item 6).

Reference parity: grpc_serde.cc / grpc_bytebuffer_stream.cc splice tensor
bytes into the wire without intermediate copies; here send writes array
memoryviews straight to the socket and receive reconstructs np.frombuffer
views into the receive buffer.  Includes the >=100 MB throughput
measurement the verdict asked for.
"""

import socket
import time

import numpy as np
import pytest

from paddle_tpu.distributed import rpc


def _echo_server():
    return rpc.Server("127.0.0.1:0", lambda msg: msg)


def test_roundtrip_structure_and_dtypes():
    srv = _echo_server()
    try:
        cli = rpc.Client(srv.endpoint)
        msg = {
            "op": "send_var",
            "grads": [np.arange(12, dtype=np.float32).reshape(3, 4),
                      np.ones((2, 2), np.float64)],
            "ids": np.array([3, 1, 2], np.int64),
            "meta": {"step": 7, "names": ("w", "b"),
                     "empty": np.zeros((0,), np.float32)},
        }
        out = cli.call(msg)
        assert out["op"] == "send_var" and out["meta"]["step"] == 7
        assert out["meta"]["names"] == ("w", "b")
        np.testing.assert_array_equal(out["grads"][0], msg["grads"][0])
        np.testing.assert_array_equal(out["grads"][1], msg["grads"][1])
        np.testing.assert_array_equal(out["ids"], msg["ids"])
        assert out["grads"][0].dtype == np.float32
        assert out["grads"][1].dtype == np.float64
        assert out["meta"]["empty"].shape == (0,)
        cli.close()
    finally:
        srv.stop()


def test_received_arrays_are_writable():
    """Optimizer handlers update received tensors in place."""
    srv = _echo_server()
    try:
        cli = rpc.Client(srv.endpoint)
        out = cli.call({"w": np.zeros((8,), np.float32)})
        out["w"] += 1.0                      # must not raise
        assert out["w"].sum() == 8.0
        cli.close()
    finally:
        srv.stop()


def test_non_contiguous_and_scalar_passthrough():
    srv = _echo_server()
    try:
        cli = rpc.Client(srv.endpoint)
        a = np.arange(24, dtype=np.float32).reshape(4, 6)[:, ::2]
        out = cli.call({"a": a, "s": 3.5, "n": None})
        np.testing.assert_array_equal(out["a"], a)
        assert out["s"] == 3.5 and out["n"] is None
        cli.close()
    finally:
        srv.stop()


def test_restricted_unpickler_still_guards_control():
    """A malicious frame must still be rejected — tensor payloads bypass
    pickle entirely, control skeletons stay restricted."""
    import pickle

    srv = _echo_server()
    try:
        host, port = rpc.parse_endpoint(srv.endpoint)
        s = socket.create_connection((host, port))
        evil = pickle.dumps(ValueError("boom"))  # non-allowlisted class
        s.sendall(rpc._LEN.pack(len(evil)) + evil)
        # server drops the connection (unpickling error) without executing
        head = s.recv(8)
        assert head == b""                       # closed, no reply
        s.close()
    finally:
        srv.stop()


def test_throughput_100mb():
    """>=100 MB tensor payload round trip; print MB/s (one-way payload
    crossed the loopback twice).  The point is that 100 MB frames WORK
    and come back whole; the rate is printed and not asserted: a CPU
    loopback timing under six test workers is no speed (ROADMAP, aim
    1)."""
    srv = _echo_server()
    try:
        cli = rpc.Client(srv.endpoint, timeout=120)
        payload = np.random.RandomState(0).randint(
            0, 255, size=(100 * 1024 * 1024 // 4,)).astype(np.float32)
        assert payload.nbytes >= 100 * 1024 * 1024
        cli.call({"warm": payload[:1024]})
        t0 = time.perf_counter()
        out = cli.call({"w": payload})
        dt = time.perf_counter() - t0
        mb = payload.nbytes / 1e6
        rate = 2 * mb / dt                      # client->server->client
        print("rpc throughput: %.0f MB payload, %.2f s round trip, "
              "%.0f MB/s" % (mb, dt, rate))
        assert out["w"].nbytes == payload.nbytes
        np.testing.assert_array_equal(out["w"][:1000], payload[:1000])
        cli.close()
    finally:
        srv.stop()
