"""paddle.distributed.rpc — socket RPC + master rendezvous
(SURVEY §2.3 rpc row)."""
import operator
import os
import socket
import subprocess
import sys

import pytest

from paddle_tpu.distributed import rpc


@pytest.fixture
def single_world():
    rpc.init_rpc("solo", rank=0, world_size=1)
    yield
    rpc.shutdown()


class TestSingleWorld:
    def test_self_call_sync(self, single_world):
        assert rpc.rpc_sync("solo", operator.add, args=(2, 3)) == 5

    def test_async_future(self, single_world):
        fut = rpc.rpc_async("solo", operator.mul, args=(6, 7))
        assert fut.wait() == 42
        assert fut.result() == 42

    def test_remote_exception_propagates(self, single_world):
        with pytest.raises(ZeroDivisionError):
            rpc.rpc_sync("solo", operator.truediv, args=(1, 0))

    def test_worker_info(self, single_world):
        info = rpc.get_current_worker_info()
        assert info.name == "solo" and info.rank == 0
        assert rpc.get_worker_info("solo").endpoint == info.endpoint

    def test_double_init_raises(self, single_world):
        with pytest.raises(RuntimeError, match="already"):
            rpc.init_rpc("again", rank=0, world_size=1)

    def test_reinit_after_shutdown(self):
        rpc.init_rpc("a", rank=0, world_size=1)
        rpc.shutdown()
        rpc.init_rpc("b", rank=0, world_size=1)
        assert rpc.rpc_sync("b", operator.neg, args=(4,)) == -4
        rpc.shutdown()


def test_two_real_processes_rpc(tmp_path):
    """Rank 0 executes functions on rank 1 through real sockets, with the
    master-endpoint rendezvous assembling the worker table."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        master = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    env.pop("PADDLE_TRAINER_ID", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "1", "--nproc_per_node", "2",
         os.path.join(os.path.dirname(__file__), "_rpc_worker.py"), master],
        capture_output=True, text=True, env=env, timeout=180,
        cwd="/root/repo")
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "rank0 rpc_ok" in out.stdout
    assert "rank1 served_ok" in out.stdout
